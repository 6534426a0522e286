"""Compile the serving main path for a TPU v5e that is described, not
attached.

libtpu's compiler is installed beside the CPU backend, so XLA:TPU and
Mosaic compile here exactly what the chip would run and refuse what the
chip would refuse (unaligned tiles, too much VMEM, a program that does
not fit).  Nothing executes, so these tests say nothing about results or
times; ``chip_smoke.py`` does that on the chip.

The topology is described inside a module fixture — never at import, in
a ``skipif`` or in ``parametrize`` — because only one process may load
libtpu at a time: under pytest-xdist every worker imports this file, and
only the worker that runs these tests loads the library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.splitter import SplitEngine
from repro.kernels import ops
from repro.models.audio_encoder import AudioEncCfg, init_audio_encoder

CFG = AudioEncCfg()          # published widths: 128 mels x 100 frames
B = 8                        # one (8, 128·m) fp32 tile row-block


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no libtpu, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def stage(one_chip):
    """The split engine plus its weights and a mel batch as shapes placed
    on the described chip."""
    on_chip = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                             sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init_audio_encoder(CFG, jax.random.PRNGKey(0))))
    mel = on_chip(jax.ShapeDtypeStruct((B, CFG.frames, CFG.n_mels),
                                       jnp.float32))
    return SplitEngine(CFG), params, mel


@pytest.mark.parametrize("k", range(CFG.n_blocks))
def test_wire_roundtrip_compiles_at_published_boundary(stage, one_chip, k):
    engine, params, mel = stage
    boundary = jax.eval_shape(engine._edge_exec(k), params, mel)
    x = jax.ShapeDtypeStruct(boundary.shape, jnp.float32, sharding=one_chip)
    compiled = ops.wire_roundtrip.lower(x, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()   # Mosaic, not interpreted
    assert compiled.out_info.shape == boundary.shape


# every k runs an edge stage; the server stage exists for k < L only, so
# its deepest case is the last block boundary
@pytest.mark.parametrize("side,k", [
    ("edge", 0), ("edge", CFG.n_blocks // 2), ("edge", CFG.n_blocks),
    ("server", 0), ("server", CFG.n_blocks // 2),
    ("server", CFG.n_blocks - 1)])
def test_split_stage_compiles(stage, one_chip, side, k):
    engine, params, mel = stage
    boundary = jax.eval_shape(engine._edge_exec(k), params, mel)
    if side == "edge":
        compiled = engine._edge_exec(k).lower(params, mel).compile()
        assert compiled.out_info.shape == boundary.shape
    else:
        # the server stage takes the (received) boundary activation
        x = jax.ShapeDtypeStruct(boundary.shape, boundary.dtype,
                                 sharding=one_chip)
        compiled = engine._server_exec(k).lower(params, x).compile()
        assert compiled.out_info.shape == (B, CFG.d_embed)
