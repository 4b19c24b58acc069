"""Compile the sweep's Pallas tick kernels for a described TPU v5e.

Nothing here runs on a chip: ``jax.experimental.topologies`` describes a
``v5e:2x2`` host and the TPU compiler (installed with libtpu) compiles
for one of its chips.  That catches what interpret mode cannot — a
primitive Mosaic has no lowering for, a block the tiling refuses — at
the paper's B=512 shapes, with ``interpret=False``, so a kernel that
would fail to compile on the chip fails here first.

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library, and a
test worker that merely imports this file must not take it.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import sharding_ctx
from repro.core import scenarios
from repro.core import sweep_jax
from repro.core.spec import CampaignSpec
from repro.core.sweep import _prepare
from repro.kernels import ops

B = 512          # the planning-grid lane width the sweep runs on the chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # no compiler logs under TMPDIR
    prev_cache = jax.config.jax_enable_compilation_cache
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep such entries out of it
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log


def _engine(spec):
    lanes = [_prepare(spec, seed)[1] for seed in range(B)]
    return sweep_jax.JaxSweepEngine(lanes, use_pallas=True)


@pytest.fixture(scope="module")
def paper_engine():
    return _engine(CampaignSpec())


def _struct(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _kernel_args(eng, name, B=B):
    i32, f32 = jnp.int32, jnp.float32
    G, W, P = eng.G, eng.W, eng.Pn
    return {
        "campaign_preempt": [((B * G, W + 2), i32), ((B * G,), i32)],
        "campaign_match": [((B, G), i32), ((B,), i32)],
        "campaign_advance": [((B * G, W), i32), ((B * G, W), i32)],
        "campaign_bill": [((B, G), i32), ((B, G), f32), ((G, P), f32)],
    }[name]


# B = 1020 is the planning grid's width, the benchmark's widest: its
# 10,200 preempt and advance rows take ten row blocks
@pytest.mark.parametrize("lanes", [B, 1020])
@pytest.mark.parametrize("name", ["campaign_preempt", "campaign_match",
                                  "campaign_advance", "campaign_bill"])
def test_campaign_kernel_compiles_natively(one_chip, paper_engine, name,
                                           lanes):
    fn = getattr(ops, name)
    args = [_struct(one_chip, shape, dtype)
            for shape, dtype in _kernel_args(paper_engine, name, lanes)]
    compiled = jax.jit(
        lambda *a: fn(*a, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("spec", [CampaignSpec(),
                                  scenarios.dataplane_burst()],
                         ids=["paper", "dataplane-burst"])
def test_scan_compiles_with_native_kernels(one_chip, monkeypatch, spec):
    """The whole jitted scan, as ``JaxSweepEngine.run`` dispatches it on
    a TPU: the kernel wrappers' interpret policy is steered to "native"
    here, since this process's backend is the CPU."""
    eng = _engine(spec)
    monkeypatch.setattr(sharding_ctx, "on_tpu", lambda: True)
    (buf,), kw = eng._scan_call()
    compiled = sweep_jax._scan_campaigns.lower(
        _struct(one_chip, buf.shape, buf.dtype),
        **dict(kw, use_pallas=True)).compile()
    # four tick kernels, each a custom call (preempt runs twice a tick)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 4
    # the kernels keep their names (the benchmark finds them by these),
    # and each preemption call site carries its scope in the metadata
    kernels = re.findall(r"%(campaign_\w+?)\.\d+ = .*?op_name=\"([^\"]*)\"",
                         text)
    assert {k for k, _op in kernels} == {
        "campaign_preempt", "campaign_match", "campaign_advance",
        "campaign_bill"}
    sites = {op.split("/preempt_")[1].split("/")[0]
             for k, op in kernels if k == "campaign_preempt"}
    assert sites == {"to_target", "sampled"}
