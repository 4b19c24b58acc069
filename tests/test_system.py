"""End-to-end behaviour tests for the paper's system: the full campaign
replay, elastic training across pod-count changes (subprocess with faked
devices), sharded-MoE equivalence, and the dry-run machinery itself."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_py(code, devices=8, timeout=420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_paper_campaign_end_to_end():
    """The flagship reproduction: all paper claims in one run (fast: pure
    python simulation)."""
    from repro.core.spec import paper_spec, run_solo
    res, ctl = run_solo(paper_spec(), 2021)
    assert 14500 <= res["accel_days"] <= 17500
    assert 52000 <= res["cost"] <= 60000
    assert 2.7 <= res["eflop_hours_fp32"] <= 3.4
    assert res["preemptions"] > 0                  # spot is spot
    assert res["jobs_finished"] > 50000
    # operational sequence happened in order
    log = "\n".join(ctl.log)
    assert log.index("scale_to(2000)") < log.index("CE OUTAGE") \
        < log.index("resume at 1000")


@pytest.mark.slow
def test_elastic_pod_change_preserves_training(tmp_path):
    """2 pods -> preemption -> 1 pod -> checkpoint-restore continuation;
    loss keeps improving and params stay finite. Runs in a subprocess with
    4 faked devices (pod_shape (2,1), 2 pods)."""
    out = _run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import REDUCED_SHAPE, RunConfig, get_reduced
        from repro.core.elastic import ElasticRunner, PodPool
        from repro.checkpoint import Checkpointer
        from repro.data import make_batch
        from repro.launch import steps as st
        from repro import sharding as sh
        from repro.models import init_params
        from repro.optim import adamw_init
        from repro.sharding_ctx import use_mesh

        cfg = get_reduced("yi-9b")
        run = RunConfig(model=cfg, shape=REDUCED_SHAPE,
                        compute_dtype="float32", remat=False)
        params = jax.device_get(init_params(cfg, jax.random.PRNGKey(0)))
        opt = jax.device_get(adamw_init(params))

        def builder(mesh):
            fn = st.make_train_step(cfg, run)
            psh = sh.param_shardings(params, mesh)
            osh = sh.opt_shardings(opt, mesh)
            jf = jax.jit(fn, in_shardings=(psh, osh, None),
                         out_shardings=(psh, osh, None))
            def wrapped(p, o, b):
                with use_mesh(mesh):
                    return jf(p, o, b)
            return wrapped

        ck = Checkpointer(r"{tmp_path}", keep=2)
        runner = ElasticRunner(builder, params, opt, pod_shape=(2, 1),
                               checkpointer=ck)
        pool = PodPool()
        pool.on_change(lambda n: runner.ensure(max(n, 1)))
        pool.join("pod-a"); pool.join("pod-b")
        assert runner.n_pods == 2, runner.n_pods

        losses = []
        for step in range(6):
            m = runner.step(make_batch(cfg, REDUCED_SHAPE, step))
            losses.append(float(m["loss"]))
        runner.checkpoint(6); ck.wait()

        pool.preemption_notice("pod-b")
        runner.handle_preemption(6)
        pool.leave("pod-b")                       # spot reclaim
        assert runner.n_pods == 1
        for step in range(6, 12):
            m = runner.step(make_batch(cfg, REDUCED_SHAPE, step))
            losses.append(float(m["loss"]))
        assert all(np.isfinite(losses)), losses
        # 12 tiny-batch steps are noisy; assert stability (no divergence)
        # across the pod change rather than monotone descent
        assert sum(losses[6:]) / 6 < sum(losses[:6]) / 6 + 0.5, losses
        assert runner.rebuilds == 3, runner.rebuilds  # 1 pod -> 2 -> 1
        print("LOSSES", losses[0], losses[-1], runner.rebuilds)
    """, devices=4)
    assert "LOSSES" in out


@pytest.mark.slow
def test_sharded_moe_equivalence_multidevice():
    _run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import MoEConfig
        from repro.models import moe as moe_mod
        from repro.models.moe_sharded import apply_moe_sharded
        from repro.sharding_ctx import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        moe = MoEConfig(num_experts=8, top_k=2, d_ff_expert=32,
                        capacity_factor=8.0)
        p = moe_mod.init_moe(jax.random.PRNGKey(1), 16, moe)
        x = jax.random.normal(jax.random.PRNGKey(2), (8, 4, 16))
        y0, _ = moe_mod._apply_moe_naive(p, x, moe)
        y1, _ = jax.jit(lambda p, x: apply_moe_sharded(p, x, moe,
                                                       "swiglu", mesh))(p, x)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   atol=3e-5, rtol=3e-5)
        # gradients flow through the explicit all-to-alls
        g = jax.grad(lambda p: apply_moe_sharded(p, x, moe, "swiglu",
                                                 mesh)[0].sum())(p)
        assert all(np.isfinite(np.asarray(l)).all()
                   for l in jax.tree.leaves(g))
        print("MOE OK")
    """, devices=8)


@pytest.mark.slow
def test_dryrun_machinery():
    """run_cell lowers+compiles a real cell on the 256-chip mesh and emits
    sane roofline terms (the fast whisper decode cell)."""
    out = _run_py("""
        import json
        from repro.launch.dryrun import run_cell
        r = run_cell("whisper-large-v3", "decode_32k")
        assert r["status"] == "ok"
        assert r["n_chips"] == 256
        assert r["hlo_parsed"]["dot_flops"] > 0
        assert r["roofline"]["bottleneck"] in ("compute", "memory",
                                               "collective")
        print("DRYRUN", json.dumps(r["roofline"]))
    """, devices=512, timeout=600)
    assert "DRYRUN" in out


def test_hlo_parser_on_known_module():
    """Parser unit test: dot flops, while multipliers, promoted all-reduce."""
    from repro.analysis import hlo
    text = """\
HloModule test, num_partitions=4

%add_promoted (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %s = f32[] add(%x, %y)
}

%body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %iv = s32[] get-tuple-element(%p), index=0
  %x = f32[8,16] get-tuple-element(%p), index=1
  %w = f32[16,16] constant(0)
  %d = f32[8,16] dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16] all-reduce(%d), replica_groups={}, to_apply=%add_promoted
  ROOT %t = (s32[], f32[8,16]) tuple(%iv, %ar)
}

%cond (p: (s32[], f32[8,16])) -> pred[] {
  %p = (s32[], f32[8,16]) parameter(0)
  %iv = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(12)
  ROOT %lt = pred[] compare(%iv, %c), direction=LT
}

ENTRY %main (a: f32[8,16]) -> f32[8,16] {
  %a = f32[8,16] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8,16]) tuple(%zero, %a)
  %w = (s32[], f32[8,16]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8,16] get-tuple-element(%w), index=1
}
"""
    res = hlo.analyze(text)
    # dot: 2*8*16*16 = 4096 flops x 12 trips (trips from the cond constant)
    assert res["dot_flops"] == 4096 * 12
    # promoted f32 all-reduce: 8*16*4 bytes halved, x 12 trips
    assert res["collective_bytes"] == (8 * 16 * 4 // 2) * 12
