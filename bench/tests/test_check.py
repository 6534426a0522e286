"""The comparison that decides ``correct``, driven through a whole run
of a tiny configuration on the CPU with the chip check skipped.

The program's own path passes; the control (the reference computed in
bfloat16) put in the program's place fails; and each fault a serving
cell can have, planted in the timed path, turns ``correct`` false."""
import json
import os

import numpy as np
import pytest

import harness

DATA = os.path.join(os.path.dirname(__file__), "data")
SEED = 2 ** 33 + 7


@pytest.fixture(scope="module", autouse=True)
def cpu_cache(tmp_path_factory):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    harness.CACHE_DIR = str(tmp_path_factory.mktemp("jax_cache"))


def run(cell, **kw):
    with open(os.path.join(DATA, "tiny.spec.json")) as f:
        spec = json.load(f)
    return harness.run(spec, cell, SEED, 1.5, 0, require_chip=False,
                       traffic_dir=DATA, **kw)


@pytest.mark.parametrize("cell", ["tiny.sat", "tiny.steady"])
def test_program_is_correct_and_control_is_not(cell):
    out = run(cell)
    checks = out["checks"]
    assert out["correct"], checks
    assert out["failed"] == 0
    assert list(out)[-1] == "checks"
    ctl = run(cell, control=True)
    assert not ctl["correct"], ctl["checks"]
    gap = ctl["checks"]["embed_gap_mean"]
    assert gap["value"] > gap["limit"]


def alter_answers(system):
    """An answer altered where it is produced: every embedding of the
    interior split point comes back shifted."""
    eng = system.gw.engine
    run_batch = eng.run_batch_async

    def altered(params, mel, k):
        z, wire = run_batch(params, mel, k)
        return (z.at[:, 0].add(0.1) if 0 < k < eng.cfg.n_blocks else z), wire

    eng.run_batch_async = altered


def rings_unchanged(system):
    """A step that leaves its state unchanged: ring ingest is dropped."""
    system.backend.insert_batch = lambda *a, **kw: None


def half_the_answers(system):
    """Half of each tick's answers never delivered."""
    gw = system.gw
    collect = gw.tick_collect
    gw.tick_collect = lambda plan: collect(plan)[::2]


@pytest.mark.parametrize("fault", [alter_answers, rings_unchanged,
                                   half_the_answers])
def test_faults_turn_correct_false(fault):
    out = run("tiny.sat", inject=fault)
    assert not out["correct"], out["checks"]


def test_refuses_without_a_chip():
    import subprocess
    import sys
    root = os.path.dirname(harness.BENCH)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "audio.serve.sat", "--seed", "1", "--seconds", "1"],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_warm_plan_covers_every_tick():
    plan = harness.warm_plan([8, 4, 0], range(1, 9), 8)
    sizes = {sum(c.values()) for c in plan}
    assert sizes == set(range(1, 9))
    assert all(n > 0 for c in plan for n in c.values())
    full = harness.warm_plan([8, 4, 0], [64], 64)
    assert {sum(c.values()) for c in full} == {64}
    assert len({tuple(sorted(c)) for c in full}) == 7
    assert np.all([sum(c.values()) == 64 for c in full])
