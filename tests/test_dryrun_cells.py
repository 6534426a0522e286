"""Dry-run machinery on a small mesh (subprocess): one cell per family,
single- and multi-pod, asserting compile success + roofline fields."""
import json

import pytest

CODE = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'
import jax
from repro.launch.mesh import make_mesh
from repro.launch.dryrun import build_and_compile
mesh = make_mesh({mesh_shape}, {mesh_axes})
rec = build_and_compile('{arch}', '{shape}', mesh, overrides={overrides})
r = rec['roofline']
assert r['compute_s'] > 0 and r['bottleneck'] in ('compute', 'memory',
                                                  'collective')
assert rec['collectives']['collective_bytes'] >= 0
assert rec['memory'].get('peak_memory_in_bytes', 1) > 0
print('CELL-OK', '{arch}', '{shape}', r['bottleneck'])
"""


def _run(subproc, arch, shape, *, overrides, multi_pod=False, devices=16):
    mesh_shape = (2, 2, 4) if multi_pod else (4, 4)
    mesh_axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    out = subproc(CODE.format(
        devices=devices, arch=arch, shape=shape,
        mesh_shape=mesh_shape, mesh_axes=mesh_axes, n_axes=len(mesh_shape),
        overrides=overrides), devices=devices)
    assert "CELL-OK" in out


# reduced layer counts keep CPU compiles fast; shapes stay FULL-size inputs
SMALL = {"n_layers": 4}
SMALL_HY = {"n_layers": 7, "hybrid_period": 3}


@pytest.mark.parametrize("arch,shape,ovr", [
    ("qwen3-1.7b", "train_4k", SMALL),
    ("gemma2-2b", "prefill_32k", SMALL),          # sliding+softcap
    ("arctic-480b", "train_4k", {"n_layers": 2}), # MoE EP + dense residual
    ("mamba2-780m", "long_500k", SMALL),          # SSM decode 500k
    ("zamba2-1.2b", "decode_32k", SMALL_HY),      # hybrid decode
])
def test_single_pod_cells(subproc, arch, shape, ovr):
    _run(subproc, arch, shape, overrides=ovr)


@pytest.mark.parametrize("arch,shape,ovr", [
    ("qwen3-1.7b", "train_4k", SMALL),
    ("kimi-k2-1t-a32b", "train_4k", {"n_layers": 2}),
])
def test_multi_pod_cells(subproc, arch, shape, ovr):
    _run(subproc, arch, shape, overrides=ovr, multi_pod=True)
