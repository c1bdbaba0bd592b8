"""The sharded resident verify program, compiled for a described TPU
v5e 2x2 host (no chip attached: jax.experimental.topologies), exactly as
aot.ExecutableRegistry._build builds a sharded executable.

Signature verification is lane-parallel with no term across lanes, so
the compiler's partition over the batch axis has to be local to each
chip: every chip runs the program on its own quarter of the lanes, no
collective, no replicated ladder. A device trace on the four-chip cell
shows it at run time (benchmark/layers/cross_chip_op_share.py); this
holds every PR to it at no chip time. One file, the topology described
inside a fixture: only the worker that is given this file loads the
TPU's compiler.
"""

import re

import numpy as np
import pytest

LANES = 2048  # the second launch of a 10,000-validator commit: 512 a chip
COLLECTIVE = re.compile(
    r"\b(all-gather|all-reduce|all-to-all|reduce-scatter"
    r"|collective-permute|collective-broadcast|send|recv)(-start|-done)?\("
)


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def compiled(topo):
    import jax
    from jax.sharding import Mesh

    from cometbft_tpu.crypto.tpu import aot, ed25519_batch as eb

    mesh = Mesh(np.array(topo.devices), ("batch",))
    shapes = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in
              [((8, LANES), np.uint32), ((24, LANES), np.uint32)]]
    return aot.ExecutableRegistry()._build(
        eb.verify_kernel_resident, shapes, donate_from=1, sharded=True,
        mesh=mesh,
    ), len(topo.devices)


def test_every_chip_runs_its_own_quarter_of_the_lanes(compiled):
    exe, chips = compiled
    assert chips == 4
    text = exe.as_text()
    head = text.split("\n", 1)[0]
    assert "num_partitions=4" in head
    per = LANES // chips
    assert (f"(u32[8,{per}]" in head and f"u32[24,{per}]" in head
            and f"->pred[{per}]" in head), head
    # the program the trace will name, which benchmark/opcount.PROGRAMS
    # ("verify") has to find
    assert re.search(r"HloModule jit_\w*verify\w*", head), head
    # no chip is handed the whole batch anywhere in its program
    assert f"[8,{LANES}]" not in text and f"[24,{LANES}]" not in text


def test_the_partition_has_no_collective(compiled):
    exe, _ = compiled
    found = sorted({m.group(0) for m in COLLECTIVE.finditer(exe.as_text())})
    assert found == [], f"the sharded verify program talks across chips: {found}"
