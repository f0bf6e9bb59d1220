"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e
that is described, not attached, at road64k widths.

Interpret mode (every other kernel test) cannot see what the TPU's
compiler refuses: slices Mosaic cannot lower, or more scoped VMEM than
a kernel may use.  These compiles can, with no chip.  The widths are
those of the road64k preset's build (``data/roads.py``, seed 0), which
is the same on every host: 128 fragment tiles at maxf = 496, DRA
pieces at caps 8 and 32, group closures at m2 = 1024 (6 groups on
level 1, 4 on level 2), a top closure over S_top = 1867 boundary nodes
(d2 and the label rows are S_top + 1 = 1868 wide), and serve batches
up to 1024 queries.  The road250k preset adds wider group closures
(10 groups at m2 = 2048, 6 at 2048, 3 at 4096) and a top closure over
6,470 boundary nodes.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and under several
test workers only the one running this file does.
"""
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import device_engine
from repro.kernels import first_hop as fh
from repro.kernels import floyd_warshall as fw
from repro.kernels import ops
from repro.kernels import label_merge as lm
from repro.kernels import minplus_twoside as ts

S_TOP = 1867
S_TOP_250K = 6470
BATCH = 1024


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent
    compilation cache off (entries compiled for a described chip
    cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs on disk
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except (ImportError, RuntimeError, ValueError) as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; assert a Mosaic kernel
    made it into the program."""
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch,n", [(128, 496),     # fragment tiles
                                     (6211, 8),      # DRA pieces
                                     (75, 32),
                                     (6, 1024)])     # group closures
def test_fw_batch_next_compiles(one_chip, batch, n):
    """The witness closure at every tile width the road64k build runs:
    one diagonal block up to 256 wide, panels above."""
    _compile(fw.fw_batch_next_blocked, one_chip, (batch, n, n))


@pytest.mark.parametrize("batch,n", [(10, 2048), (3, 4096)])
def test_blocked_witness_closure_compiles(one_chip, batch, n):
    """The witness closure at road250k's group widths, where a whole
    tile's operands would not fit VMEM."""
    _compile(fw.fw_batch_next_blocked, one_chip, (batch, n, n))


def test_top_first_hops_compile(one_chip):
    """road250k's top witnesses: first hops over the 6,470-node top
    closure on the device."""
    shape = (S_TOP_250K, S_TOP_250K)
    _compile(fh.first_hop_pallas, one_chip, shape, shape, shape)


def test_fw_blocked_compiles(one_chip):
    """The top closure: blocked FW (diagonal tile + min-plus panels)."""
    _compile(jax.jit(fw.fw_blocked), one_chip, (S_TOP, S_TOP))


@pytest.mark.parametrize("kernel", [ts.minplus_twoside_pallas,
                                    ts.minplus_twoside_argmin_pallas])
def test_minplus_twoside_compiles(one_chip, kernel):
    """The cross-query combine against d2, distance and witness forms."""
    w = S_TOP + 1
    _compile(kernel, one_chip, (BATCH, w), (w, w), (BATCH, w))


@pytest.mark.parametrize("groups,m2", [(6, 1024), (10, 2048), (3, 4096)])
def test_minplus_twoside_grouped_compiles(one_chip, groups, m2):
    """The grouped same-group leg contraction at every closure width
    the cells run, over the most q tiles a 1,024-row bucket can take:
    the closure stack is read whole, each tile's block chosen by
    scalar prefetch."""
    tiles = BATCH // ts.GROUPED_BQ + groups
    rows = (tiles * ts.GROUPED_BQ, m2)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in ((rows, jnp.float32),
                          ((groups + 1, m2, m2), jnp.float32),
                          (rows, jnp.float32), ((tiles,), jnp.int32),
                          ((), jnp.int32))]
    text = ts.minplus_twoside_grouped_pallas.lower(*args).compile(
    ).as_text()
    assert text.count("tpu_custom_call") == 1


def test_label_merge_compiles(one_chip):
    """The hub-label merge over full-width label rows."""
    w = S_TOP + 1
    _compile(lm.label_merge_pallas, one_chip, (BATCH, w), (BATCH, w))


def _leg_text(sharding, groups, width, m2, monkeypatch):
    """The compiled text of the same-group leg in its kernel layout for
    a 512-row bucket: both sides scattered into tiles of one group
    each, in m2 group coordinates, one grouped twoside for the level."""
    monkeypatch.setattr(ops, "_use_pallas", lambda force: (True, False))

    def leg(closure, row_s, grp_s, pos_s, row_t, grp_t, pos_t):
        dix = types.SimpleNamespace(sf_closure=(closure,))
        return device_engine._hier_leg(dix, 0, row_s, grp_s, pos_s,
                                       row_t, grp_t, pos_t)

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    side = (arg((512, width)), arg((512, width), jnp.int32),
            arg((512, width), jnp.int32))
    return jax.jit(leg).lower(arg((groups + 1, m2, m2)), *side,
                              *side).compile().as_text()


def _assert_one_grouped_call(text, m2):
    """One grouped contraction for the level, and no [q, m2] copy of a
    side (the masked per-group layout made one per group)."""
    assert text.count("tpu_custom_call") == 1
    assert "minplus_twoside_grouped_pallas" in text
    assert f"f32[512,{m2}]" not in text


@pytest.mark.parametrize("groups,width", [(6, 56),      # level 1
                                          (4, 432)])    # level 2
def test_same_group_leg_compiles(one_chip, groups, width, monkeypatch):
    """road64k's legs: m2 = 1024, at each grouping level's row width."""
    text = _leg_text(one_chip, groups, width, 1024, monkeypatch)
    _assert_one_grouped_call(text, 1024)


def test_same_group_leg_compiles_at_4096(one_chip, monkeypatch):
    """road250k's third grouping level: 3 groups at m2 = 4096, rows
    1,616 wide (the second level's boundary slots)."""
    text = _leg_text(one_chip, 3, 1616, 4096, monkeypatch)
    _assert_one_grouped_call(text, 4096)


@pytest.mark.parametrize("groups,m2,width", [
    (6, 1024, 568),        # road64k: a level's lift
    (6, 1024, S_TOP + 1),  # road64k: the resident rows
    (10, 2048, 1096),      # road250k: the three levels' lifts
    (6, 2048, 1616),
    (3, 4096, 2168)])
def test_minplus_grouped_compiles(one_chip, groups, m2, width):
    """The grouped lift product at every block width the cells run,
    none a multiple of 128 but one, over the most q tiles a 1,024-row
    bucket can take: the output block is the block's whole width."""
    tiles = BATCH // ts.GROUPED_BQ + groups
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in (((tiles * ts.GROUPED_BQ, m2), jnp.float32),
                          ((groups + 1, m2, width), jnp.float32),
                          ((tiles,), jnp.int32), ((), jnp.int32))]
    text = ts.minplus_grouped_pallas.lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1


def test_lift_compiles_without_a_loop(one_chip, monkeypatch):
    """road250k's level-2 lift in its kernel layout for a 1,024-row
    bucket: one grouped product against the l2row stack, and no loop
    left of the gather's chunk loop."""
    monkeypatch.setattr(ops, "_use_pallas", lambda force: (True, False))

    def lift(l2row, row, grp, pos):
        dix = types.SimpleNamespace(l2row=(l2row,))
        return device_engine._lift_compact(dix, 0, row, grp, pos)

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(lift).lower(
        arg((4, 4096, 2168)), arg((BATCH, 1616)),
        arg((BATCH, 1616), jnp.int32), arg((BATCH, 1616), jnp.int32)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "minplus_grouped_pallas" in text
    assert " while(" not in text
