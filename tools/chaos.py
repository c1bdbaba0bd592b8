"""Standalone chaos harness against the supervised verify plane.

Nine modes:

* default (smoke) — crypto/faults.py run_chaos_smoke: a fast,
  deterministic walk of every degradation-ladder rung (transient retry,
  OOM chunk-shrink + recovery, hedged verification, failed-batch triage,
  breaker trip/probe/re-admit), asserting ground-truth verdict equality
  at every step. Finishes in well under a second.

* --devices N --kill K — crypto/faults.py run_chaos_multidevice: the
  partial-mesh degradation rung. On an N-fault-domain topology, device
  K alone is injected with hang → oom → corrupt (FaultPlan.device /
  CBFT_FAULT_DEVICE); asserts zero wrong verdicts, continued
  device-path service on the survivors (no node-wide CPU fallback, no
  global breaker trip), quarantine of K, and re-admission by K's own
  canary. Deterministic under --seed. Runs on the virtual CPU mesh, so
  it needs no hardware (tier-1 CI runs it via
  XLA_FLAGS=--xla_force_host_platform_device_count).

* --sharded — crypto/faults.py run_chaos_sharded: the sharded-mesh
  degradation rung. Megabatches route as ONE multi-device sharded
  program over an N-domain mesh (routing mode "sharded"); device K is
  then killed mid-flow with a program-fatal injected failure. Asserts
  ground-truth verdicts with zero wrong answers, attribution of the
  failure to the offending domain (exactly K quarantined, topology
  mirror set, shard plan re-sliced to N-1 for the in-flight retry),
  degraded sharded throughput ≥ 0.6 × (N-1)/N of the full-mesh rate,
  and re-slice back to N after K's canary re-admits it. Needs N
  visible jax devices — exported via XLA_FLAGS automatically.

* --memory-guard — crypto/faults.py run_chaos_memory_guard: the
  proactive-vs-reactive OOM proof. An allocator-modeled OOM fault
  (CBFT_FAULT_OOM_ABOVE semantics) first runs WITHOUT the memory
  plane's pre-dispatch guard — every cap halving costs a real
  RESOURCE_EXHAUSTED — then WITH it: the guard clamps the chunk cap
  from the modeled HBM headroom before dispatch, so zero
  RESOURCE_EXHAUSTED ever reaches the supervisor while verdicts stay
  ground-truth-exact.

* --overload — crypto/faults.py run_chaos_overload: the QoS admission
  rung. A steady consensus workload rides through a 10x
  blocksync+mempool flood: with the default class ladder, consensus
  p99 stays inside 2x max(unloaded p99, one dispatch quantum), zero
  consensus sheds/drops, the floods shed/drop, the brownout controller
  trips and re-admits once the flood stops, and every non-rejected
  future carries ground-truth verdicts. The SAME flood is then replayed
  with CBFT_QOS_CLASSES=off and must blow the same latency bound — the
  contrast that proves the admission layer is load-bearing.

* --wire — crypto/faults.py run_chaos_wire: the wire-ledger attribution
  rung. Every jax.device_put is stretched by a seeded jitter draw (a
  jittery link) around an otherwise clean dispatch; asserts the ledger
  blames the slowdown on the h2d transfer phase (grew by at least half
  the injected sleep) and NOT compute (stays flat), with every verdict
  still ground-truth-exact. Fast and deterministic; runs in tier-1 CI.

* --stale-model — crypto/faults.py run_chaos_stale_model: the
  decision-plane staleness proof. A clean regime lets the routing
  ledger's cost model converge; injected link jitter then leaves the
  model's predictions behind, the windowed MAPE crosses the trip
  level, and the anomaly watchdog must fire exactly ONE incident
  capture (flight-recorder dump) and re-arm once walls recover —
  proving the watchdog detects a stale cost model without flapping.
  The scheduler runs the PRICED live router: the trip must also roll
  routing back to the threshold ladder exactly once, and recovery must
  re-admit the priced argmin (hysteretic rollback guard, ISSUE 16).

* --service — crypto/faults.py run_chaos_service: the
  verify-as-a-service rung. One daemon (VerifyScheduler + VerifyService
  on a Unix socket), 32 flood clients + 4 consensus clients over real
  sockets: four clients are killed abruptly mid-flight (their futures
  must resolve via the local-CPU fallback with reason "disconnected",
  a survivor sharing the SAME coalesced flush must still get correct
  verdicts, and the server must meter the disconnects and keep
  serving); then a blocksync+mempool flood at ~2.5x dispatch capacity
  must leave consensus p99 inside its bound while the merged queue's
  QoS layer sheds/drops flood (honest rejections over the wire, never
  wrong verdicts), brownout trips and re-admits, payload stays at
  <= 128 bytes/lane, and the service drains to zero pending.

* --ha — crypto/faults.py run_chaos_ha: the HA verify-fleet rung.
  Three authenticated verifyd replicas behind ONE HAVerifier under
  committee load: a rolling drain-restart of every replica (typed
  ST_DRAINING refusals deterministically exercise the per-request
  failover rung — zero wrong verdicts, ZERO local-CPU fallbacks, drains
  attributed "draining" not "disconnected"), one hard kill (failover
  within a bounded gap, attributed "disconnected"), one socket
  blackhole (breaker quarantine with zero pick leakage, then
  re-admission by the endpoint's OWN health probe), a wrong-key client
  refused typed ERR_UNAUTHORIZED on every endpoint without ever
  reaching a scheduler, and an aggregate-throughput comparison against
  a single daemon.

* --adversary — crypto/adversary.py run_chaos_adversary: the
  workload-side attack rung. A synthesized committee (default 512
  validators, real ed25519 keys and canonical vote sign-bytes) storms
  the full stack: 25% byzantine vote flood per height, valset churn
  every 8 heights, equivocation (double-sign evidence) bursts through
  the evidence tenant, non-validator vote spam through the mempool
  tenant, and one mid-storm verifyd kill/restart across the service
  boundary. Asserts zero wrong verdicts (construction-time ground
  truth + CPU oracle), exact triage attribution of every injected
  byzantine signature, the ceil(log2 n)+1 triage pass bound, consensus
  p99 within 2x the unloaded bound, a healthy breaker (bad signatures
  are not device incidents), and the client's full disconnected ->
  reconnect -> re-register -> indexed recovery walk. With --soak it
  walks the committee ladder (128/512/1k/4k) instead — the slow tier.

* --soak — crypto/faults.py run_chaos_soak: a randomized fault schedule
  (exceptions, hangs, silent verdict corruption, sudden death, jitter,
  OOM, transient flaps) over N simulated blocks through a supervised
  VerifyScheduler.

Both print a JSON summary; exit status is non-zero if any node-path
invariant broke: a wrong verdict released, a future lost, or the
breaker failing to re-admit the backend after faults stop.

Default inner backend is "cpu" (self-contained exercise of the
supervisor machinery); pass --inner tpu on a host with a live device
plane to drive the real dispatch path under injected faults. The fast
smoke runs in tier-1 CI (tests/test_adaptive_dispatch.py); the
`slow`-marked soak test in tests/test_supervisor.py runs the soak.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--soak", action="store_true",
                    help="run the long randomized soak instead of the "
                         "fast deterministic ladder smoke (default)")
    ap.add_argument("--blocks", type=int, default=50,
                    help="[soak] simulated blocks to soak (default 50)")
    ap.add_argument("--batch", type=int, default=48,
                    help="[soak] signatures per block (default 48)")
    ap.add_argument("--seed", type=int, default=1234,
                    help="fault-schedule RNG seed (default 1234; the "
                         "smoke uses it for its key material too)")
    ap.add_argument("--inner", default="cpu",
                    help='backend under the faults: "cpu" (default) or '
                         '"tpu" (requires a live device plane)')
    ap.add_argument("--dispatch-timeout-ms", type=int, default=500,
                    help="[soak] supervisor watchdog budget per dispatch "
                         "(default 500; raise for a real TPU link)")
    ap.add_argument("--probe-base-ms", type=int, default=20,
                    help="[soak] canary probe backoff base (default 20)")
    ap.add_argument("--submitters", type=int, default=3,
                    help="[soak] concurrent submitter threads per block "
                         "(default 3)")
    ap.add_argument("--oom-rate", type=float, default=None,
                    help="override CBFT_FAULT_OOM_RATE for ad-hoc runs "
                         "of a faulty node (exported to the env)")
    ap.add_argument("--transient-n", type=int, default=None,
                    help="override CBFT_FAULT_TRANSIENT_N for ad-hoc "
                         "runs of a faulty node (exported to the env)")
    ap.add_argument("--devices", type=int, default=1,
                    help="fault domains for the multi-device rung; >1 "
                         "runs run_chaos_multidevice instead of the "
                         "single-device smoke (default 1)")
    ap.add_argument("--kill", type=int, default=2,
                    help="[multi-device] fault-domain index to inject "
                         "(default 2)")
    ap.add_argument("--sharded", action="store_true",
                    help="run the sharded-mesh rung: kill one domain "
                         "mid-sharded-megabatch-flow and assert "
                         "attribution, re-slice, and the degraded "
                         "throughput bound (uses --devices/--kill)")
    ap.add_argument("--rounds", type=int, default=4,
                    help="[sharded] timed megabatch rounds per "
                         "throughput phase (default 4)")
    ap.add_argument("--overload", action="store_true",
                    help="run the QoS overload rung: consensus stays "
                         "inside its latency bound through a "
                         "blocksync+mempool flood, the floods "
                         "shed/drop, brownout trips and re-admits; the "
                         "same flood with CBFT_QOS_CLASSES=off starves "
                         "consensus")
    ap.add_argument("--flood-s", type=float, default=1.5,
                    help="[overload] flood duration per phase "
                         "(default 1.5)")
    ap.add_argument("--service", action="store_true",
                    help="run the verify-as-a-service rung: 32+4 "
                         "clients over a Unix socket against one "
                         "coalescing daemon — disconnect containment, "
                         "QoS under flood, brownout re-admission, "
                         "bytes/lane bound, zero wrong verdicts "
                         "(uses --flood-s)")
    ap.add_argument("--ha", action="store_true",
                    help="run the HA verify-fleet rung: 3 authenticated "
                         "replicas behind one HAVerifier — rolling "
                         "drain-restart with zero CPU fallbacks, hard "
                         "kill inside the failover-gap bound, blackhole "
                         "quarantine + probe re-admission, wrong-key "
                         "refusal, fleet-vs-single throughput")
    ap.add_argument("--replicas", type=int, default=3,
                    help="[ha] daemon replicas in the fleet (default 3)")
    ap.add_argument("--memory-guard", action="store_true",
                    help="run the proactive-vs-reactive OOM rung "
                         "(memory plane pre-dispatch guard)")
    ap.add_argument("--lanes-threshold", type=int, default=256,
                    help="[memory-guard] allocator-model lane threshold "
                         "above which the injected OOM fires "
                         "(default 256)")
    ap.add_argument("--wire", action="store_true",
                    help="run the wire-ledger attribution rung: a "
                         "jittery link (stretched device_put) must show "
                         "up in the ledger's h2d phase, not compute")
    ap.add_argument("--jitter-ms", type=float, default=25.0,
                    help="[wire] per-put jitter draw ceiling "
                         "(default 25)")
    ap.add_argument("--stale-model", action="store_true",
                    help="run the decision-plane staleness rung: "
                         "injected link jitter must trip the routing "
                         "ledger's anomaly watchdog, fire exactly one "
                         "incident dump, and re-arm after recovery")
    ap.add_argument("--stale-jitter-ms", type=float, default=300.0,
                    help="[stale-model] per-dispatch jitter draw "
                         "ceiling for the stale regime (default 300)")
    ap.add_argument("--adversary", action="store_true",
                    help="run the adversarial-committee rung: byzantine "
                         "vote flood + valset churn + equivocation "
                         "storm + spam + mid-storm verifyd restart, "
                         "zero wrong verdicts and exact attribution "
                         "(with --soak: the 128/512/1k/4k committee "
                         "ladder instead)")
    ap.add_argument("--committee", type=int, default=512,
                    help="[adversary] validator-committee size "
                         "(default 512)")
    ap.add_argument("--heights", type=int, default=16,
                    help="[adversary] storm heights (default 16)")
    ap.add_argument("--byz-rate", type=float, default=0.25,
                    help="[adversary] byzantine signature rate per "
                         "height (default 0.25)")
    args = ap.parse_args()

    if args.inner == "cpu":
        # self-contained: no device plane required
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # env-driven fault knobs: picked up by any FaultPlan.from_env() in
    # this process (e.g. a faulty node backend installed elsewhere)
    if args.oom_rate is not None:
        os.environ["CBFT_FAULT_OOM_RATE"] = str(args.oom_rate)
    if args.transient_n is not None:
        os.environ["CBFT_FAULT_TRANSIENT_N"] = str(args.transient_n)

    if args.adversary:
        from cometbft_tpu.crypto.adversary import (
            campaign_ok,
            run_adversary_ladder,
            run_chaos_adversary,
        )

        if args.soak:
            summary = run_adversary_ladder(
                seed=args.seed, sizes=(128, 512, 1024, 4096),
                heights=args.heights, byzantine_rate=args.byz_rate,
            )
            print(json.dumps(summary, indent=2, default=str))
            ok = summary["ok"]
            print("CHAOS ADVERSARY-SOAK", "PASS" if ok else "FAIL",
                  "seed=%d" % args.seed)
            return 0 if ok else 1
        summary = run_chaos_adversary(
            seed=args.seed, committee=args.committee,
            heights=args.heights, byzantine_rate=args.byz_rate,
        )
        print(json.dumps(summary, indent=2, default=str))
        ok = campaign_ok(summary)
        print("CHAOS ADVERSARY", "PASS" if ok else "FAIL",
              "seed=%d" % args.seed)
        return 0 if ok else 1

    if args.soak:
        from cometbft_tpu.crypto.faults import run_chaos_soak

        summary = run_chaos_soak(
            n_blocks=args.blocks,
            batch=args.batch,
            seed=args.seed,
            inner=args.inner,
            dispatch_timeout_ms=args.dispatch_timeout_ms,
            probe_base_ms=args.probe_base_ms,
            n_submitters=args.submitters,
        )
        print(json.dumps(summary, indent=2))
        ok = (
            summary["wrong_verdicts"] == 0
            and summary["lost_futures"] == 0
            and summary["readmitted"]
            and summary["device_resumed_after_recovery"]
        )
        print("CHAOS SOAK", "PASS" if ok else "FAIL",
              "seed=%d" % args.seed)
        return 0 if ok else 1

    if args.wire:
        from cometbft_tpu.crypto.faults import run_chaos_wire

        summary = run_chaos_wire(
            seed=args.seed, jitter_ms=args.jitter_ms,
        )
        print(json.dumps(summary, indent=2))
        # run_chaos_wire asserts the invariants inline; re-check the
        # headline ones here so --wire reads like the other rungs
        ok = (
            summary["ok"]
            and summary["injected_jitter_ms"] > 0
            and summary["h2d_delta_ms"]
            >= 0.5 * summary["injected_jitter_ms"]
            and summary["compute_delta_ms"]
            <= max(5.0, 0.25 * summary["injected_jitter_ms"])
        )
        print("CHAOS WIRE", "PASS" if ok else "FAIL",
              "seed=%d" % args.seed)
        return 0 if ok else 1

    if args.stale_model:
        from cometbft_tpu.crypto.faults import run_chaos_stale_model

        summary = run_chaos_stale_model(
            seed=args.seed, jitter_ms=args.stale_jitter_ms,
        )
        print(json.dumps(summary, indent=2))
        # run_chaos_stale_model asserts the invariants inline; re-check
        # the headline ones so --stale-model reads like the other rungs
        ok = (
            summary["ok"]
            and summary["wrong_verdicts"] == 0
            and summary["trips"] == 1
            and summary["anomaly_fires"] == 1
            and summary["incident_dumps"] == 1
            and summary["rearmed"]
            and summary["router_rollbacks"] == 1
            and summary["router_readmits"] == 1
            and summary["router_live"] == "priced"
        )
        print("CHAOS STALE-MODEL", "PASS" if ok else "FAIL",
              "seed=%d" % args.seed)
        return 0 if ok else 1

    if args.overload:
        from cometbft_tpu.crypto.faults import run_chaos_overload

        summary = run_chaos_overload(
            seed=args.seed, inner=args.inner, flood_s=args.flood_s,
        )
        print(json.dumps(summary, indent=2))
        ok = (
            summary["wrong_verdicts"] == 0
            and summary["latency_ok"]
            and summary["consensus_sheds"] == 0
            and summary["consensus_drops"] == 0
            and summary["consensus_backpressure_timeouts"] == 0
            and summary["flood_sheds"] >= 1
            and summary["flood_drops"] >= 1
            and summary["rejected"] >= 1
            and summary["brownout"]["trips"] >= 1
            and summary["brownout"]["readmissions"] >= 1
            and not summary["brownout"]["disabled"]
            and summary["readmitted"]
            and summary["starved_without_qos"]
        )
        print("CHAOS OVERLOAD", "PASS" if ok else "FAIL",
              "seed=%d" % args.seed)
        return 0 if ok else 1

    if args.service:
        from cometbft_tpu.crypto.faults import run_chaos_service

        summary = run_chaos_service(seed=args.seed, flood_s=args.flood_s)
        print(json.dumps(summary, indent=2))
        ok = (
            summary["wrong_verdicts"] == 0
            and summary["latency_ok"]
            and summary["consensus_sheds"] == 0
            and summary["consensus_drops"] == 0
            and summary["flood_sheds"] >= 1
            and summary["flood_drops"] >= 1
            and summary["rejected"] >= 1
            and summary["disconnect_fallbacks"] >= 4
            and summary["killed_client_fallbacks"] >= 1
            and summary["disconnects_metered"] >= 1
            and summary["brownout"]["trips"] >= 1
            and summary["readmitted"]
            and summary["pending_after"] == 0
            and summary["bytes_per_lane_ok"]
            and summary["timeline_ok"]
            and summary["incident_dump_ok"]
        )
        print("CHAOS SERVICE", "PASS" if ok else "FAIL",
              "seed=%d" % args.seed)
        return 0 if ok else 1

    if args.ha:
        from cometbft_tpu.crypto.faults import run_chaos_ha

        summary = run_chaos_ha(seed=args.seed, replicas=args.replicas)
        print(json.dumps(summary, indent=2, default=str))
        ok = (
            summary["wrong_verdicts"] == 0
            and summary["rolling_failovers"] >= args.replicas
            and summary["rolling_cpu_fallbacks"] == 0
            and summary["rolling_readmits"] == args.replicas
            and summary["kill_failovers"] >= 1
            and summary["kill_attributed_disconnects"] >= 1
            and summary["failover_gap_p99_ms"]
            <= summary["failover_gap_bound_ms"]
            and summary["blackhole_quarantined"]
            and summary["quarantine_picks_leaked"] == 0
            and summary["probe_readmitted"]
            and summary["probe_readmissions"] >= 1
            and summary["failover_reasons"].get("draining", 0)
            >= args.replicas
            and summary["failover_reasons"].get("disconnected", 0) >= 1
            and summary["evil_unauthorized"] >= 1
            and summary["server_auth_rejects"] >= 1
            and summary["evil_requests_served"] == 0
        )
        print("CHAOS HA", "PASS" if ok else "FAIL",
              "seed=%d" % args.seed)
        return 0 if ok else 1

    if args.memory_guard:
        from cometbft_tpu.crypto.faults import run_chaos_memory_guard

        summary = run_chaos_memory_guard(
            seed=args.seed, inner=args.inner,
            lanes_threshold=args.lanes_threshold,
        )
        print(json.dumps(summary, indent=2))
        # run_chaos_memory_guard asserts the invariants inline; re-check
        # the headline ones here so --memory-guard reads like the others
        ok = (
            summary["wrong_verdicts"] == 0
            and summary["reactive_ooms"] > 0
            and summary["guarded_ooms"] == 0
            and summary["guarded_shrinks"] == 0
            and summary["guard_cap"] <= args.lanes_threshold
            and summary["state_final"] == summary["expected"]["state_final"]
        )
        print("CHAOS MEMORY-GUARD", "PASS" if ok else "FAIL",
              "seed=%d" % args.seed)
        return 0 if ok else 1

    if args.sharded:
        # the sharded program genuinely shards over N jax devices, so
        # the virtual device plane is required even for --inner cpu;
        # must land in the env before anything imports jax
        devices = args.devices if args.devices > 1 else 8
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={devices}",
        )
        from cometbft_tpu.crypto.faults import run_chaos_sharded

        summary = run_chaos_sharded(
            devices=devices, kill=args.kill, seed=args.seed,
            inner=args.inner, rounds=args.rounds,
        )
        print(json.dumps(summary, indent=2))
        killed = f"dev{args.kill}"
        # run_chaos_sharded asserts the invariants inline; re-check the
        # headline ones so --sharded reads like the other rungs
        ok = (
            summary["wrong_verdicts"] == 0
            and summary["cpu_routed"] == 0
            and set(summary["quarantines"]) == {killed}
            and summary["quarantined_only_kill"]
            and summary["topology_mirrored_quarantine"]
            and summary["sharded_reslices"] >= 1
            and summary["resliced_shards"] == devices - 1
            and summary["throughput_ok"]
            and summary["degraded_rate_sigs_s"]
            >= summary["throughput_bound_sigs_s"]
            and summary["readmit_probe_ok"]
            and summary["restored_shards"] == devices
            and all(
                s == summary["expected"]["final_state"]
                for s in summary["final_states"].values()
            )
        )
        print("CHAOS SHARDED", "PASS" if ok else "FAIL",
              "seed=%d" % args.seed)
        return 0 if ok else 1

    if args.devices > 1:
        if args.inner != "cpu":
            # a real device plane needs N visible devices; the virtual
            # CPU mesh is how the rung runs hardware-free
            os.environ.setdefault(
                "XLA_FLAGS",
                f"--xla_force_host_platform_device_count={args.devices}",
            )
        from cometbft_tpu.crypto.faults import run_chaos_multidevice

        summary = run_chaos_multidevice(
            devices=args.devices, kill=args.kill, seed=args.seed,
            inner=args.inner,
        )
        print(json.dumps(summary, indent=2))
        killed = f"dev{args.kill}"
        ok = (
            summary["wrong_verdicts"] == 0
            and summary["cpu_routed"] == 0
            and set(summary["quarantines"]) == {killed}
            and summary["readmissions"].get(killed, 0) >= 3
            and summary["redistributions"] >= 3
            and all(
                p["quarantined_only_kill"]
                and p["survivors_grew"]
                and p["state_while_quarantined"]
                == summary["expected"]["state_while_quarantined"]
                and p["readmit_probe_ok"]
                for p in summary["phases"].values()
            )
            and all(
                s == summary["expected"]["final_state"]
                for s in summary["final_states"].values()
            )
        )
        print("CHAOS MULTIDEVICE", "PASS" if ok else "FAIL",
              "seed=%d" % args.seed)
        return 0 if ok else 1

    from cometbft_tpu.crypto.faults import run_chaos_smoke

    summary = run_chaos_smoke(seed=args.seed, inner=args.inner)
    print(json.dumps(summary, indent=2))
    ok = (
        summary["wrong_verdicts"] == 0
        and summary["retries"] >= 1
        and summary["chunk_shrinks"] >= 1
        and summary["chunk_recoveries"] >= 1
        and summary["hedge_fires"] >= 1
        and summary["hedge_wins"] >= 1
        and summary["hedge_divergence"] == 0
        and summary["triage_runs"] >= 1
        and summary["triage_clean_futures_ok"]
        and not summary["triage_tripped_breaker"]
        and summary["triage_divergence"] == 0
        and summary["state_broken"] == summary["expected"]["state_broken"]
        and summary["probe_ok"]
        and summary["state_final"] == summary["expected"]["state_final"]
    )
    print("CHAOS SMOKE", "PASS" if ok else "FAIL",
              "seed=%d" % args.seed)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
