"""The mixed-key commit cell: the plan a pure function of config, params
and seed with the curves interleaved in the set's order; the three
forged commits refused by the references; the three secp256k1 readers on
what the generator carries (and nothing where a program keeps no book by
curve); and the toy cell end to end on the CPU platform through a real
node."""

import copy

import pytest

from benchmark import run, secp_opcount
from benchmark.traffic import mixed_commit as gen
from tests.benchmark.test_sync_cell import (  # noqa: F401 - a fixture
    _restore_process_state,
)
from tests.conftest import mixed_commit_toy

SEED = 2_150_000_777  # more than 32 signed bits hold
NEW_READERS = ("secp256k1_verify_roofline", "secp256k1_kernel_us_per_lane",
               "secp_pack_us_per_lane")


@pytest.fixture(scope="module")
def plan():
    return gen.build(*mixed_commit_toy(), SEED)


def test_the_curves_interleave_and_the_forged_commits_are_refused(plan):
    config, params = mixed_commit_toy()
    types = [v.pub_key.type() for v in plan["valset"].validators]
    assert types.count("secp256k1") == config["secp256k1_validators"]
    assert len(types) == config["validators"]
    # neither curve is contiguous in the set's order
    runs = sum(1 for a, b in zip(types, types[1:]) if a != b)
    assert runs >= 3
    assert plan["want"] == [True] * config["pool_commits"]
    assert {k: c["want"] for k, c in plan["forged"].items()} == {
        "ed25519_flip": False, "secp256k1_flip": False,
        "secp256k1_high_s": False,
    }
    for kind, (_, curve) in gen.FORGED.items():
        seat = plan["forged"][kind]["seat"]
        assert types[seat] == curve
    bid, height, commit = plan["pool"][0]
    high = plan["forged"]["secp256k1_high_s"]
    sig = high["entry"][2].signatures[high["seat"]].signature
    assert sig[:32] == commit.signatures[high["seat"]].signature[:32]


def test_the_plan_is_a_pure_function_of_config_params_and_seed(plan):
    again = gen.build(*mixed_commit_toy(), SEED)
    sigs = lambda p: [[cs.signature for cs in c.signatures]  # noqa: E731
                      for _, _, c in p["pool"]]
    assert sigs(plan) == sigs(again)


def _after(curves, wire_lanes=(0, 0)):
    snap = lambda n: {"wire": {"lanes": {"resident": n}}}  # noqa: E731
    trace = {
        "programs": {"jit__secp256k1_verify_core": 0.030,
                     "jit__verify_core_resident": 0.012},
        "counters": {"before": snap(wire_lanes[0]),
                     "after": snap(wire_lanes[1])},
    }
    after = {"bench": {"spans_s": {} if curves is None else
                       {"curves": curves}, "device_kind": "TPU v5 lite"}}
    return after, trace


def test_the_secp256k1_readers_read_the_carried_book():
    curves = {"ed25519": {"device_lanes": 6000, "host_lanes": 0,
                          "pack_s": 0.006},
              "secp256k1": {"device_lanes": 4000, "host_lanes": 0,
                            "pack_s": 0.020}}
    after, trace = _after(curves, (1000, 3000))
    got = {name: run.load_module("layers", name).read({}, after, trace)
           for name in NEW_READERS}
    # 2,000 lanes between the edges, 40 % of them secp256k1
    assert got["secp256k1_kernel_us_per_lane"] == pytest.approx(
        0.030 / 800 * 1e6)
    least = secp_opcount.least_seconds_per_lane(
        {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9})["seconds"]
    assert got["secp256k1_verify_roofline"] == pytest.approx(
        100 * least * 1e6 / got["secp256k1_kernel_us_per_lane"])
    assert got["secp_pack_us_per_lane"] == pytest.approx(5.0)
    # the accepted reader of both curves' programs
    assert run.load_module("layers", "kernel_us_per_lane").read(
        {}, after, trace) == pytest.approx(0.042 / 2000 * 1e6)


def test_a_program_without_the_book_gives_the_readers_nothing():
    after, trace = _after(None, (1000, 3000))
    for name in NEW_READERS:
        assert run.load_module("layers", name).read({}, after, trace) is None


def test_the_toy_cell_end_to_end_on_the_cpu_platform(
        monkeypatch, _restore_process_state):  # noqa: F811
    """12 validators (6 secp256k1), floors lowered (4 for ed25519 on the
    node, 1 for secp256k1), one fault domain: a commit's two curves in
    one stream on one CPU device. The profiler is left out, as in
    test_run_contract.py."""
    monkeypatch.delenv("CBFT_WARM_BOOT", raising=False)
    monkeypatch.setenv("CBFT_TPU_SECP_MIN_BATCH", "1")
    cell = run.resolve_cell("mega10k-mixed-commit")
    cell = dict(cell, config=copy.deepcopy(cell["config"]),
                traffic=copy.deepcopy(cell["traffic"]))
    config, params = mixed_commit_toy()
    cell["config"].update(config)
    cell["config"]["crypto"].update(min_batch=4, max_chunk=128,
                                    fault_domains=1)
    cell["traffic"]["params"].update(params)

    def start(self):
        self.before = self.plane.books.snapshot()
        self.started_at = run.time.monotonic()

    def stop(self):
        if not self.stopped and self.started_at is not None:
            self.after = self.plane.books.snapshot()
        self.stopped = True

    monkeypatch.setattr(run.SubWindowTrace, "_start", start)
    monkeypatch.setattr(run.SubWindowTrace, "stop", stop)
    monkeypatch.setattr(run.SubWindowTrace, "reduce", lambda self: None)
    monkeypatch.setitem(cell["cell"], "trace", {"after_s": 0.2,
                                                "seconds": 0.5})
    device = {"platform": "cpu", "kind": "cpu", "count": 8}
    line = run.run_cell(cell, SEED, 2.0, True, device, expect_platform="cpu")
    assert line["correct"] is False  # not a TPU, and says so
    # a secp256k1 launch takes ~2 s on XLA:CPU: one commit or two
    assert line["attempted"] >= 1 and line["failed"] == 0
    got = line["metrics"]
    absent = {"kernel_us_per_lane", "secp256k1_verify_roofline",
              "secp256k1_kernel_us_per_lane"}
    if line["attempted"] < 10:
        absent.add("host_cpu_ms_per_ksig")
    assert set(got) == set(cell["cell"]["layers"]) - absent
    assert got["compiles_in_window"]["value"] == 0
    # the node's own chain's votes (~4 host lanes a second) beside a
    # commit's 12 device lanes
    assert got["device_lane_share"]["value"] >= 25
    assert got["secp_pack_us_per_lane"]["value"] > 0
    assert got["pack_us_per_lane"]["value"] > 0
