"""chip_smoke.py's control flow at toy size on the CPU platform, so chip
budget is never spent on a typo — and the compile-cache helper's two
cases. The platform assertion is parameterised: here the "tpu" backend
resolves the virtual CPU mesh on purpose (JAX_PLATFORMS=cpu)."""

import os
import sys
import tempfile
from types import SimpleNamespace

import pytest

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
import chip_smoke  # noqa: E402

from cometbft_tpu.crypto.tpu import aot  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 7


@pytest.fixture(autouse=True)
def _restore_process_state():
    """default_new_node with the tpu backend installs process-wide
    settings (one node per process is its contract); put them back so
    the legs' node does not leak into the tests that follow."""
    yield
    from cometbft_tpu.crypto import batch as cryptobatch
    from cometbft_tpu.crypto.tpu import calibrate, keystore, mesh

    cryptobatch.set_default_backend("cpu")
    mesh.configure_chunk_cap(None)
    calibrate.set_table_path(None)
    keystore.default_store().invalidate()


def test_no_tpu_exits_nonzero_and_prints_no_result(capsys):
    assert chip_smoke.main([]) == chip_smoke.NO_TPU_EXIT
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err and "'cpu'" in out.err


def test_corrupt_spoils_nonadjacent_lanes_both_verifiers_reject():
    vals, privs = chip_smoke.make_valset(64, SEED, "t")
    _, commit = chip_smoke.make_commit(vals, privs, 9, SEED)
    items, spoiled = chip_smoke.corrupt(
        chip_smoke.commit_items(vals, commit)
    )
    assert len(spoiled) >= 5
    assert all(b - a > 1 for a, b in zip(spoiled, spoiled[1:]))
    want = chip_smoke.cpu_oracle(items)
    assert [i for i, ok in enumerate(want) if not ok] == spoiled


def test_legs_at_toy_size_on_the_cpu_platform(monkeypatch):
    """node → megacommit → blocksync → service → audit: 120-validator
    commit (a vote of the node's own chain may ride a flush along, and
    must not push it over the 128 bucket into a compile), 2 blocks x 64
    validators, 2 service clients x 2 rounds. The routing floor is
    lowered to 64 so the toy flushes take the device route; everything
    else is what the chip run does (waiting for a warm boot is covered
    below)."""
    from cometbft_tpu.crypto import service as servicelib

    monkeypatch.delenv("CBFT_WARM_BOOT", raising=False)
    legs = {}
    with tempfile.TemporaryDirectory() as tmp:
        node, legs["node"] = chip_smoke.leg_node(
            os.path.join(tmp, "home"), expect_platform="cpu",
            min_height=2, min_batch=64,
        )
        try:
            assert legs["node"]["plane"]["platform"] == "cpu"
            assert legs["node"]["warm_boot"] is None
            assert legs["node"]["crypto"]["dispatch_timeout_ms"] == 60000
            legs["megacommit"] = chip_smoke.leg_megacommit(node, 120, SEED)
            legs["blocksync"] = chip_smoke.leg_blocksync(node, 2, 64, SEED)
            legs["service"] = chip_smoke.leg_service(
                tmp, 2, 2, 64, SEED, expect_platform="cpu",
                row_verifier=servicelib.dispatch_rows,
            )
            books = chip_smoke.audit(node, legs, expect_platform="cpu")
        finally:
            node.stop()
    mega = legs["megacommit"]
    assert mega["resident_lanes_on_device"] == 240
    assert [x["keys"] for x in mega["submit"]] == [
        "keys_intact", "keys_intact", "key_swapped", "key_swapped",
    ]
    assert all(f["taken"] == "single" for f in mega["flushes"])
    # the virtual mesh has 8 devices: the keystore's gather table is a
    # single-device route, so every flush here ships keyed
    assert mega["indexed_wire_expected"] is False
    assert not legs["blocksync"]["valset_resident"]
    assert legs["service"]["lanes_on_device"] == 2 * 2 * 64
    assert books["lanes_by_taken_route"].get("single", 0) >= 4 * 120
    causes = books["lanes_on_cpu_pool_by_cause"]
    assert causes["audited"] == books["supervisor"]["audit_lanes"]
    assert causes["triage_confirmed"] >= mega["supervised_bad_lanes"] > 0


def test_node_leg_waits_for_the_nodes_own_warm_boot(monkeypatch):
    """With the node's warm boot switched on (its env switch wins over
    the smoke's config) the node leg waits for the warm-boot thread —
    ladder, then calibration — and fails on its error instead of running
    the legs against a half-warm plane. The body is replaced: the real
    one is minutes of XLA:CPU compile."""
    import cometbft_tpu.node.node as nodemod

    monkeypatch.setenv("CBFT_WARM_BOOT", "background")
    ran = []

    def quick_warm(config):
        def body(stop_event):
            ran.append(int(config.crypto.min_batch))
            return [{"kernel": "stub", "bucket": 64, "compile_s": 0.0}]

        aot.start_warm_boot(
            aot.warm_boot_mode(config.crypto.warm_boot), body=body
        )

    monkeypatch.setattr(nodemod, "_warm_tpu_kernels", quick_warm)
    with tempfile.TemporaryDirectory() as tmp:
        node, rec = chip_smoke.leg_node(
            os.path.join(tmp, "home"), expect_platform="cpu",
            min_height=1, min_batch=64,
        )
        node.stop()
    assert ran == [64]
    assert rec["warm_boot"] == [
        {"kernel": "stub", "bucket": 64, "compile_s": 0.0}
    ]
    assert rec["wire_lanes_before_legs"]["auto"] \
        + rec["wire_lanes_before_legs"]["single"] == 8  # the canary's

    def failing_warm(config):
        def body(stop_event):
            raise RuntimeError("no such bucket")

        aot.start_warm_boot(
            aot.warm_boot_mode(config.crypto.warm_boot), body=body
        )

    monkeypatch.setattr(nodemod, "_warm_tpu_kernels", failing_warm)
    with tempfile.TemporaryDirectory() as tmp:
        with pytest.raises(chip_smoke.SmokeFailure, match="warm boot failed"):
            chip_smoke.leg_node(
                os.path.join(tmp, "home"), expect_platform="cpu",
                min_height=1, min_batch=64,
            )


def test_multichip_leg_places_buffers_on_their_own_devices():
    from cometbft_tpu.crypto.tpu import topology

    topo = topology.DeviceTopology.detect()
    assert len(topo) > 1
    prev = topology.default_topology()
    topology.set_default_topology(topo)
    try:
        rec = chip_smoke.leg_multichip(
            SimpleNamespace(verify_topology=topo), SEED
        )
    finally:
        topology.set_default_topology(prev)
    assert rec["plan_shards"] == len(topo)
    assert len(rec["sharded_output_devices"]) == len(topo)
    assert rec["scoped_domain"] == f"dev{len(topo) - 1}"


class TestCompileCacheHelper:
    def test_variable_set_nothing_set_in_code(self, monkeypatch):
        import jax

        calls = []
        monkeypatch.setattr(
            jax.config, "update", lambda *a, **k: calls.append(a)
        )
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert aot.compile_cache_dir() == "/some/dir"
        assert aot.exec_store_root() == "/some/dir/aot_exec"
        assert calls == []

    def test_variable_unset_is_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(_REPO, ".jax_cache")
        assert aot.compile_cache_dir() == want
        assert aot.exec_store_root() == os.path.join(want, "aot_exec")
        # and jax was told: a compile here lands in that directory
        import jax

        assert jax.config.values["jax_" "compilation_cache_dir"] == want
