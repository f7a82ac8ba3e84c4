"""chip_smoke.py's phases at a tiny size on the CPU.

The script itself refuses to run without a TPU; these tests drive its phase
functions with the gather+score kernel forced on (interpret mode off-TPU),
so every scoring path, the oracle checks and the 4-way mesh comparison are
exercised before any chip time is spent.
"""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

ROWS = 1200


def _tiny_config(**kw):
    from repro.core.boomhq import BoomHQConfig
    from repro.core.data_encoder import DataEncoderConfig
    from repro.core.rewriter import RewriterConfig

    return BoomHQConfig(
        n_clusters=8,
        encoder=DataEncoderConfig(frozen_steps=10, ae_steps=10, sample=256),
        rewriter=RewriterConfig(steps=20, refine_columns=False), **kw)


@pytest.fixture
def kernel_on(monkeypatch):
    """Every gather_score_topk call takes the Pallas kernel (interpreted
    here); jit caches are cleared so no reference-path trace is reused."""
    import jax

    from repro.kernels import gather_score

    jax.clear_caches()
    monkeypatch.setattr(gather_score, "_default_use_kernel", lambda: True)
    yield
    jax.clear_caches()


def test_main_refuses_a_cpu_backend(capsys):
    import chip_smoke

    assert chip_smoke.main([]) == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out and "no TPU" in out


def test_single_chip_paths(kernel_on):
    """Every scoring path of the one-chip run serves the mixed requests
    through the async engine and passes the oracle checks and floors."""
    import chip_smoke

    bq, _ = chip_smoke.build(ROWS, 0, 6, _tiny_config())
    ht = chip_smoke.host_table(bq.table)
    sets = {"mixed": chip_smoke.make_requests(bq.table, 3, 3, 0),
            "single": chip_smoke.make_requests(bq.table, 3, 3, 0,
                                               n_vec_used=1)}
    masks = {k: chip_smoke.oracle_masks(ht, v) for k, v in sets.items()}
    clock = chip_smoke.CompileClock()
    recorder = chip_smoke.GroupRecorder()
    try:
        seen = {}
        for name, rset, force, plan, floor, every in \
                chip_smoke.scoring_paths(bq):
            s = chip_smoke.run_path(
                bq, sets[rset], masks[rset], name=name, force=force,
                plan=plan, floor=floor, every=every, batch_size=4,
                clock=clock, recorder=recorder)
            assert s["served"] == len(sets[rset])
            seen[name] = {g for g, (uses, _) in s["groups"].items() if uses}
    finally:
        recorder.close()
    assert not seen["dense"]  # no gather kernel on the dense path
    assert "ivf.search_local_batch" in seen["candidate_local_fp32"]
    assert "ivf.search_local_batch_int8" in seen["candidate_local_int8"]
    assert "graph.search_local_batch" in seen["graph"]


def test_check_results_rejects_a_wrong_score():
    """The oracle check fails a returned score off its row's exact score."""
    import chip_smoke
    from repro.bench import datasets, queries

    table = datasets.make("part", rows=400, seed=1)
    q = queries.gen_workload(table, 1, n_vec_used=2, seed=3)[0]
    m = chip_smoke.oracle_masks(chip_smoke.host_table(table), [q])[0]
    top = np.argsort(-m)[: q.k]
    chip_smoke.check_results([q], [(top, m[top])], [m])
    with pytest.raises(chip_smoke.SmokeFailure, match="oracle"):
        chip_smoke.check_results([q], [(top, m[top] + 1e-2)], [m])


SHARD_SCRIPT = """
import json, sys
sys.path.insert(0, {root!r})
import chip_smoke
from repro.bench import datasets
from repro.core.boomhq import BoomHQ
from repro.kernels import gather_score
from test_chip_smoke import _tiny_config

gather_score._default_use_kernel = lambda: True
table = datasets.make("part", rows={rows}, seed=0)
bq = BoomHQ(table, _tiny_config(graph_degree=0))
reqs = chip_smoke.make_requests(table, 3, 3, 0)
ht = chip_smoke.host_table(table)
out = chip_smoke.shard_phase(bq, reqs, chip_smoke.oracle_masks(ht, reqs), ht,
                             n_chips=4, batch_size=4)
print(json.dumps(out))
"""


def test_shard_phase_on_four_host_devices():
    """The --chips 4 phase on a 4-device host platform: the table is
    placed on the mesh and both sharded routes agree with logical shards
    (and the exact scan with the sharded oracle)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), str(ROOT / "tests")]))
    r = subprocess.run(
        [sys.executable, "-c", SHARD_SCRIPT.format(root=str(ROOT),
                                                   rows=ROWS)],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "column 0" in r.stdout and "0:0-300" in r.stdout, r.stdout
    routes = json.loads(r.stdout.strip().splitlines()[-1])
    assert [s["route"] for s in routes] == ["dense", "sharded_local"]
    for s in routes:
        assert s["served"] == 6
