"""``chip_smoke.py`` on the CPU: it refuses to serve without a TPU, and
its phases, run at a tiny width, pass their own checks (conservation,
refine, the float32 reference, sharded vs 1-shard parity)."""
import importlib.util
import json
import os
import subprocess
import sys

import jax

from conftest import REPO
from repro.models.audio_encoder import AudioEncCfg

SMOKE = os.path.join(REPO, "chip_smoke.py")
TINY = dict(widths=(8, 8, 16, 16), strides=(1, 2, 1, 2), n_mels=16,
            frames=20, d_embed=16, groups=4)
SIZES = dict(capacity=64, clients={"interactive": 2, "standard": 4,
                                   "bulk": 6},
             per_client=8, max_batch=8)


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run([sys.executable, SMOKE], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert "{" not in r.stdout                   # no result line


def test_one_chip_phase_at_small_width(monkeypatch):
    smoke = _load()
    # the CPU runs the Pallas interpreter by design; on the chip this
    # check demands Mosaic
    monkeypatch.setattr(smoke, "wire_is_compiled", lambda *a: None)
    out = smoke.serve_one_chip(jax.devices()[:1], AudioEncCfg(**TINY),
                               **SIZES)
    assert out["frames"] == 12 * 8
    assert out["max_abs_error"] <= 1e-5          # f32 end to end on CPU
    # one executable against three launches, at k=0 and an interior k
    assert out["fused_max_abs"] <= 1e-5
    assert len(out["refine_losses"]) >= 2


def test_sharded_phase_at_small_width(subproc):
    code = f"""
import importlib.util, json, jax
spec = importlib.util.spec_from_file_location("chip_smoke", {SMOKE!r})
smoke = importlib.util.module_from_spec(spec); spec.loader.exec_module(smoke)
from repro.models.audio_encoder import AudioEncCfg
out = smoke.serve_sharded(jax.devices()[:4], AudioEncCfg(**{TINY!r}),
                          **{SIZES!r})
print(json.dumps(out))
"""
    out = json.loads(subproc(code, devices=4).strip().splitlines()[-1])
    assert out["shard_frames"] == [24, 24, 24, 24]
    assert out["max_abs_error"] <= 1e-5
    assert out["refine_rel_diff"] <= 1e-5
