"""Ahead-of-time compiles for a described TPU v5e (``v5e:2x2``).

Nothing here runs on a chip: each test lowers and compiles for a
topology that is described, not attached, so the TPU compiler refuses
here what it would refuse on the chip (block shapes that break the
(8, 128) tiling rule, too much VMEM). Covered, at SR1 widths (LSTMP
153->1024, projection 512, prune 1-1/13):

* the CSB kernel, ``csb_mvm_pallas(interpret=False)``, at every block
  size the serving path uses (32, 64, 128), and at the tiling
  ``ops.csb_tiling`` chooses for the benchmark's batches (the whole
  batch and a whole block-row a grid step) and for a batch too large to
  stage whole;
* the frame server's jitted SR1 frame step, ``cell_apply`` over
  ``PaddedCSB`` weights, under the names a profile finds it by;
* ``csb_matvec_sharded`` on a 4-device ("data", "model") mesh;
* at the widths of He et al. 2019's RNN-T (LN-LSTMP 2048 cells,
  projection 640, 4,096 outputs, 64 streams): the frame step of the
  encoder layer above the time reduction, and the greedy-decode program.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every pytest-xdist
worker imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.cells import init_state, make_cell
from repro.configs import PAPER_MODELS
from repro.core.csb_format import PaddedCSB
from repro.kernels import ops
from repro.kernels.csb_mvm import csb_mvm_pallas
from repro.kernels.csb_sharded import _sharded_fn
from repro.serve.engine import make_frame_step

SR1 = PAPER_MODELS["SR1"]
FRAMES = 8            # streams served per frame step


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _survivors(block: int) -> int:
    """Padded kernel width at SR1's rate: each pass keeps sqrt(1/13) of
    a block's rows (cols); the widest block holds about twice that."""
    return -(-int(2 * block * (1 / 13) ** 0.5) // 8) * 8


def _csb(shape, block, sharding, dtype=jnp.float32) -> PaddedCSB:
    """A ``PaddedCSB`` of shape structs at ``shape`` with square blocks."""
    br, bc = -(-shape[0] // block), -(-shape[1] // block)
    nb, pk = br * bc, _survivors(block)

    def sds(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=sharding)

    return PaddedCSB(
        vals=sds((nb, pk, pk), dtype), row_idx=sds((nb, pk), jnp.int32),
        col_idx=sds((nb, pk), jnp.int32), m=sds((nb,), jnp.int32),
        n=sds((nb,), jnp.int32), shape=tuple(shape), grid=(br, bc),
        block=(block, block))


def _sr1_shapes():
    """(out, in) of every MVM weight of both SR1 layers."""
    shapes = set()
    for layer in SR1.layers:
        graph = make_cell(layer.cell, layer.n_input, layer.n_hidden,
                          proj_dim=layer.proj)
        shapes |= {o.shape for o in graph.mvm_ops}
    return sorted(shapes)


@pytest.mark.parametrize("block", [128, 64, 32])
def test_csb_kernel_compiles_at_sr1_widths(one_chip, block):
    for shape in _sr1_shapes():
        p = _csb(shape, block, one_chip)
        bc = p.grid[1]
        x = jax.ShapeDtypeStruct((FRAMES, bc * block), jnp.float32,
                                 sharding=one_chip)
        compiled = csb_mvm_pallas.lower(
            p.vals, p.row_idx, p.col_idx, p.m, p.n, x, grid=p.grid,
            block=p.block, batch_tile=8, group=1, interpret=False).compile()
        assert "tpu_custom_call" in compiled.as_text(), shape


@pytest.mark.parametrize("shape,streams", [
    ((1024, 153), 256), ((1024, 512), 256), ((512, 1024), 256),   # SR1
    ((2048, 320), 64), ((2048, 640), 64), ((2048, 1280), 64),     # RNN-T
    ((640, 2048), 64), ((2048, 128), 64),
    ((2048, 4096), 4096),     # past the VMEM budget: split by csb_tiling
])
def test_csb_kernel_compiles_at_chosen_tiling(one_chip, shape, streams):
    p = _csb(shape, 128, one_chip)
    tb, group = ops.csb_tiling(streams, p.grid, p.block, p.pm, p.pn)
    if streams <= 256:
        assert (tb, group) == (streams, p.grid[1])
    x = jax.ShapeDtypeStruct((streams, p.grid[1] * 128), jnp.float32,
                             sharding=one_chip)
    compiled = csb_mvm_pallas.lower(
        p.vals, p.row_idx, p.col_idx, p.m, p.n, x, grid=p.grid,
        block=p.block, batch_tile=tb, group=group,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text(), shape


def test_csb_kernel_compiles_bf16_grouped(one_chip):
    """bf16 weights (sublane tile 16) and two blocks fused per step."""
    p = _csb((1024, 512), 128, one_chip, jnp.bfloat16)
    x = jax.ShapeDtypeStruct((FRAMES, 512), jnp.bfloat16, sharding=one_chip)
    compiled = csb_mvm_pallas.lower(
        p.vals, p.row_idx, p.col_idx, p.m, p.n, x, grid=p.grid,
        block=p.block, batch_tile=8, group=2, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("layer", [0, 1])
def test_sr1_frame_step_compiles(one_chip, monkeypatch, layer):
    # the kernel's interpret default asks jax.default_backend(), which is
    # the CPU here: steer the cell's MVMs onto the compiled branch
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    cfg = SR1.layers[layer]
    graph = make_cell(cfg.cell, cfg.n_input, cfg.n_hidden, proj_dim=cfg.proj)
    params = {
        name: (_csb(shape, 128, one_chip) if len(shape) == 2 else
               jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip))
        for name, shape in graph.weight_shapes().items()}
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        init_state(graph, (FRAMES,)))
    x = jax.ShapeDtypeStruct((FRAMES, cfg.n_input), jnp.float32,
                             sharding=one_chip)

    compiled = make_frame_step(graph).lower(params, state, x).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= len(graph.mvm_ops)
    # the names the benchmark's trace reduction finds them by
    assert hlo.startswith("HloModule jit_frame_step,")
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels and all(
        line.lstrip().startswith("%csb_mvm_pallas") for line in kernels)


def test_rnnt_step_and_decode_compile(one_chip, monkeypatch):
    from repro.models import transducer as T
    from repro.serve.transducer import _decode_program

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    model = T.make_transducer(320, 2048, 640, encoder_layers=8,
                              reduce_after=2, prediction_layers=2,
                              vocab=4096, embed_dim=128, joint_dim=640)
    streams = 64

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def cell(graph):
        return ({name: (_csb(shape, 128, one_chip) if len(shape) == 2
                        else sds(shape))
                 for name, shape in graph.weight_shapes().items()},
                jax.tree.map(lambda a: sds(a.shape, a.dtype),
                             init_state(graph, (streams,))))

    graph = model.encoder[model.reduce_after]       # 1,280 inputs
    params, state = cell(graph)
    hlo = make_frame_step(graph).lower(
        params, state, sds((streams, 1280))).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 9
    pred = [cell(g) for g in model.prediction]
    params = {"encoder": [], "prediction": [p for p, _ in pred],
              "embed": sds((4096, 128)),
              "joint": {"W_e": sds((640, 640)), "W_p": sds((640, 640)),
                        "b": sds((640,)), "W_out": sds((4096, 640)),
                        "b_out": sds((4096,))}}
    state = {"label": sds((streams,), jnp.int32),
             "pred": [s for _, s in pred]}
    hlo = _decode_program(model).lower(
        params, sds((4, streams, 640)), state).compile().as_text()
    assert hlo.startswith("HloModule jit_rnnt_decode,")
    # one prediction step (2 layers of 9 products) in the program
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 18 and all(
        line.lstrip().startswith("%csb_mvm_pallas") for line in kernels)


def test_sharded_csb_matvec_compiles_on_four_chips(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    shape, block = (1024, 512), 128            # SR1's U_* gates
    br, bc = shape[0] // block, shape[1] // block
    rpd, pk = br // 4, _survivors(block)
    row_map = tuple(tuple(range(d * rpd, (d + 1) * rpd)) for d in range(4))
    lead = NamedSharding(mesh, P("model"))

    def sds(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=lead)

    args = (sds((4, rpd * bc, pk, pk), jnp.float32),
            sds((4, rpd * bc, pk), jnp.int32),
            sds((4, rpd * bc, pk), jnp.int32),
            sds((4, rpd * bc), jnp.int32), sds((4, rpd * bc), jnp.int32),
            jax.ShapeDtypeStruct((FRAMES, bc * block), jnp.float32,
                                 sharding=NamedSharding(mesh,
                                                        P("data", None))))
    fn = _sharded_fn(mesh, "model", (br, bc), (block, block), rpd, row_map,
                     8, 1, False, 2)
    hlo = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "all-gather" in hlo


def test_batch_tile_off_the_tiling_rule_raises():
    """A batch tile that is neither a multiple of 8 nor the whole batch
    cannot be laid out by Mosaic: refused up front, never interpreted."""
    vals = jnp.zeros((1, 8, 8), jnp.float32)
    idx = jnp.zeros((1, 8), jnp.int32)
    cnt = jnp.zeros((1,), jnp.int32)
    x = jnp.zeros((8, 128), jnp.float32)
    with pytest.raises(ValueError, match="TPU tiling rule"):
        csb_mvm_pallas(vals, idx, idx, cnt, cnt, x, grid=(1, 1),
                       block=(128, 128), batch_tile=4, interpret=False)
