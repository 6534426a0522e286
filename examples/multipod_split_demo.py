"""2-stage split pipeline across the 'pod' mesh axis with an INT8 wire —
the TPU-native adaptation of the paper's edge/cloud split (DESIGN.md §2).

Runs on CPU with 4 fake devices:
    PYTHONPATH=src python examples/multipod_split_demo.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax
import jax.numpy as jnp

from repro.core.splitter import split_pipeline_podwise
from repro.launch.mesh import make_test_mesh


def main():
    mesh = make_test_mesh((2, 2), ("pod", "data"))
    key = jax.random.PRNGKey(0)
    d, M, mb = 64, 6, 8
    # two stage weight stacks: pod 0 holds stage 0, pod 1 stage 1
    W = 0.2 * jax.random.normal(key, (2, d, d))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
    for quant in (False, True):
        out = split_pipeline_podwise(mesh, stage_fn, W, x,
                                     quantize_wire=quant,
                                     batch_axes="data")
        want = jnp.tanh(jnp.tanh(x @ W[0]) @ W[1])
        err = float(jnp.max(jnp.abs(out - want)))
        wire = "INT8" if quant else "fp32"
        bytes_per_act = x[0].size * (1 if quant else 4)
        print(f"{wire} wire: max err {err:.5f}  "
              f"({bytes_per_act/1024:.1f} KB/microbatch crosses the pod link)")
    print("microbatches stream through: pod0 computes stage0(t) while "
          "pod1 computes stage1(t-1) — the paper's split, TPU-native.")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
