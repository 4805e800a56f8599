"""Compile rehearsals: the main path's Pallas kernels compiled for a
described (not attached) TPU v5e at the paper's Experiment-1 widths
(L=20, tpn=30, n=30, d=600 padded to 768, r=4).  Nothing runs; each test
asserts the TPU compiler accepts the kernel and that the compiled
program holds a ``tpu_custom_call``.  Interpret mode cannot show what
these do: block shapes the TPU lowering refuses, kernel bodies Mosaic
cannot lower.  They compile with x64 off, as the chip path runs.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import altgdmin_ls as ls
from repro.kernels import compress as cp
from repro.kernels import gossip_axpy as ga

L, TPN, N, D, R, BLK = 20, 30, 30, 768, 4, 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    # the chip path runs with x64 off (conftest turns it on for the
    # CPU oracle): Mosaic lowers int32 index maps and grid ids only
    with jax.enable_x64(False), jax.default_matmul_precision("highest"):
        hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    return hlo


F32 = jnp.float32
X_ = ((L, TPN, N, D), F32)
U_ = ((L, D, R), F32)
Y_ = ((L, TPN, N), F32)
B_ = ((L, TPN, R), F32)


def test_node_fused_iter_compiles(one_chip):
    _compile(lambda X, U, y: ls.node_fused_iter(X, U, y, blk_d=BLK,
                                                interpret=False),
             one_chip, X_, U_, Y_)


def test_node_task_gram_compiles(one_chip):
    _compile(lambda X, U, y: ls.node_task_gram(X, U, y, blk_d=BLK,
                                               interpret=False),
             one_chip, X_, U_, Y_)


def test_node_task_gram_serving_shape_compiles(one_chip):
    """The serving solve: one node, max_batch=32 requests, n_pad=32."""
    _compile(lambda X, U, y: ls.node_task_gram(X, U, y, blk_d=BLK,
                                               interpret=False),
             one_chip, ((1, 32, 32, D), F32), ((1, D, R), F32),
             ((1, 32, 32), F32))


def test_node_task_grad_tiles_compiles(one_chip):
    _compile(lambda X, U, B, y: ls.node_task_grad_tiles(
        X, U, B, y, blk_d=BLK, interpret=False), one_chip, X_, U_, B_, Y_)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gossip_combine_compiles(one_chip, dtype):
    """At the (8, 256) tile ``ops.gossip_combine`` uses, K=3 shifts."""
    _compile(lambda z, nb, w: ga.gossip_combine(z, nb, w, blk_rows=8,
                                                interpret=False),
             one_chip, ((48, 256), dtype), ((3, 48, 256), dtype),
             ((4,), F32))


def test_mix_rows_compiles(one_chip):
    _compile(lambda W, Z: ga.mix_rows(W, Z, blk_c=512, interpret=False),
             one_chip, ((L, L), F32), ((L, 2560), F32))


def test_compress_topk_compiles(one_chip):
    _compile(lambda M: cp.compress_topk(M, 150, interpret=False),
             one_chip, ((L, 600, R), F32))


def test_dequant_compiles(one_chip):
    _compile(lambda q, s: cp.dequant(q, s, interpret=False),
             one_chip, ((L, 600, R), jnp.int8), ((L, 1, 1), F32))


@pytest.fixture(scope="module")
def four_chips(one_chip):
    """A mesh over the described host's four chips, in the order
    ``jax.make_mesh`` gives a 2x2 v5e (``one_chip`` described it)."""
    import numpy as np
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return jax.sharding.Mesh(np.array(topo.devices)[[0, 1, 3, 2]],
                             ("nodes",))


def test_refit_on_four_chips_holds_no_cholesky_custom_call(four_chips):
    """The mesh tiers' final refit, compiled for four chips: the pallas
    engine's min-B over each chip's block of 5 nodes.  Solved through
    ``jax.scipy.linalg.solve``, that program gave its ``Cholesky``
    custom call a result layout with the two minor dims swapped, and on
    the chips every node's B came out wrong; the solve is now plain
    array ops, so no such custom call is left to be laid out."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.engine import AltgdminEngine
    eng = AltgdminEngine(backend="pallas", blk_d=BLK)
    refit = jax.shard_map(eng.minimize_B, mesh=four_chips,
                          in_specs=(P("nodes"),) * 3, out_specs=P("nodes"),
                          check_vma=False)
    nodes = NamedSharding(four_chips, P("nodes"))
    args = [jax.ShapeDtypeStruct(s, F32, sharding=nodes)
            for s in ((L, 600, R), (L, TPN, N, 600), (L, TPN, N))]
    with jax.enable_x64(False), jax.default_matmul_precision("highest"):
        hlo = jax.jit(refit).lower(*args).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert 'custom_call_target="Cholesky"' not in hlo
