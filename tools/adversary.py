"""Parameterized adversarial-committee campaigns against the verify
stack (crypto/adversary.py).

Where ``tools/chaos.py --adversary`` runs the fixed acceptance rung,
this CLI exposes every attack-plan knob for ad-hoc campaigns: committee
size, byzantine signature rate, churn cadence and fraction,
equivocation burst shape, non-validator spam volume, the service leg
and its mid-storm kill/restart height, and the seed. Prints the full
invariant summary as JSON; exit status is non-zero when any invariant
broke (a wrong verdict, inexact attribution, a blown latency bound, a
breaker trip, or a failed restart-recovery walk).

Examples:

    # the acceptance shape, but 100% byzantine
    python tools/adversary.py --byz-rate 1.0

    # a 4k-committee churn grinder, no service leg
    python tools/adversary.py --committee 4096 --heights 8 \\
        --churn-every 2 --churn-frac 0.5 --no-service

    # the committee ladder (what the bench adversary stage runs)
    python tools/adversary.py --ladder --sizes 128,512,1024
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--committee", type=int, default=512,
                    help="validator-committee size (default 512)")
    ap.add_argument("--heights", type=int, default=16,
                    help="storm heights (default 16)")
    ap.add_argument("--byz-rate", type=float, default=0.25,
                    help="byzantine signature rate per height, 0..1 "
                         "(default 0.25)")
    ap.add_argument("--churn-every", type=int, default=8,
                    help="rotate the valset every N heights; 0 disables "
                         "(default 8)")
    ap.add_argument("--churn-frac", type=float, default=0.25,
                    help="fraction of seats re-keyed per rotation "
                         "(default 0.25)")
    ap.add_argument("--equivocation-every", type=int, default=4,
                    help="double-sign evidence burst every N heights; "
                         "0 disables (default 4)")
    ap.add_argument("--equivocation-burst", type=int, default=8,
                    help="double-sign pairs per burst (default 8)")
    ap.add_argument("--spam", type=int, default=32,
                    help="non-validator votes per height; 0 disables "
                         "(default 32)")
    ap.add_argument("--no-service", action="store_true",
                    help="skip the network-boundary leg (local "
                         "scheduler/supervisor plane only)")
    ap.add_argument("--kill-height", type=int, default=None,
                    help="verifyd kill/restart height (default: "
                         "heights/2 when the service leg runs; 0 "
                         "disables the restart)")
    ap.add_argument("--seed", type=int, default=1234,
                    help="campaign RNG seed (default 1234)")
    ap.add_argument("--ladder", action="store_true",
                    help="walk the committee-size ladder instead of one "
                         "campaign (uses --sizes/--heights/--byz-rate)")
    ap.add_argument("--sizes", default="128,512,1024",
                    help="[ladder] comma-separated committee sizes "
                         "(default 128,512,1024)")
    args = ap.parse_args()

    # self-contained: no device plane required
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from cometbft_tpu.crypto.adversary import (
        AttackPlan,
        campaign_ok,
        run_adversary_ladder,
        run_campaign,
    )

    if args.ladder:
        sizes = tuple(int(s) for s in args.sizes.split(",") if s)
        summary = run_adversary_ladder(
            seed=args.seed, sizes=sizes, heights=args.heights,
            byzantine_rate=args.byz_rate, service=not args.no_service,
        )
        print(json.dumps(summary, indent=2, default=str))
        ok = summary["ok"]
        print("ADVERSARY LADDER", "PASS" if ok else "FAIL",
              "seed=%d" % args.seed)
        return 0 if ok else 1

    service = not args.no_service
    if args.kill_height is None:
        kill = (args.heights // 2) if service else None
    else:
        kill = args.kill_height if args.kill_height > 0 else None
    plan = AttackPlan(
        committee=args.committee,
        heights=args.heights,
        byzantine_rate=args.byz_rate,
        churn_every=args.churn_every,
        churn_frac=args.churn_frac,
        equivocation_every=args.equivocation_every,
        equivocation_burst=args.equivocation_burst,
        spam_per_height=args.spam,
        service=service,
        kill_restart_height=kill if service else None,
        seed=args.seed,
    )
    summary = run_campaign(plan)
    print(json.dumps(summary, indent=2, default=str))
    ok = campaign_ok(summary)
    print("ADVERSARY CAMPAIGN", "PASS" if ok else "FAIL",
          "seed=%d" % args.seed)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
