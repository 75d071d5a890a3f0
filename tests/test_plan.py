"""ParallelPlan: the single declarative source of the sharding contract.

Three gates:

* **Plan equivalence (the refactor's regression pin):** for all six
  canonical plans, the plan-generated mesh + Partitioner shardings are
  IDENTICAL to what the pre-refactor hand-kept tables produced — the
  ``LEGACY_PLANS`` dict below is a literal copy of the old
  ``tools/spmd_check.py`` PLANS table (and ``LEGACY_RULES`` of the old
  ``mesh.DEFAULT_RULES``), so a silent change to either generated side
  fails here, not on the pod.
* **Single source of truth:** spmd_check's expectation matrix is
  generated from ``PLAN_REGISTRY`` (same keys, same kwargs), the
  Partitioner built from a plan carries it, and the global-batch
  assembly (``make_array_from_single_device_arrays`` path) is bitwise
  equal to the process-local-data path it replaces.
* **The preemption drill's plumbing:** ``preempt:at_step`` +
  ``grace_ms`` parse/fire/config, the grace timer hard-exits
  ``ExitCode.PREEMPT_EXPIRED`` when the window closes (subprocess), and
  ``monitor --restart-plan`` appends the elastic relaunch flag.
"""
from __future__ import annotations

import signal
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dalle_pytorch_tpu.parallel.mesh import (DEFAULT_RULES,  # noqa: E402
                                             Partitioner, make_mesh)
from dalle_pytorch_tpu.parallel.plan import (PARTITION_RULES,  # noqa: E402
                                             PLAN_REGISTRY, TRUNK_RULES,
                                             ParallelPlan,
                                             current_topology,
                                             describe_transition,
                                             resolve_plan_args)

# Literal copy of the PRE-refactor tools/spmd_check.py PLANS table: the
# regression pin proving the generated matrix kept the old expectations.
LEGACY_PLANS = {
    "dp": dict(mesh=dict(), plan=dict()),
    "fsdp": dict(mesh=dict(fsdp=4), plan=dict()),
    "tp": dict(mesh=dict(tp=2), plan=dict()),
    "sp-ring": dict(mesh=dict(sp=2),
                    plan=dict(ring_axis="sp", sp_impl="ring", sp_size=2)),
    "sp-ulysses": dict(mesh=dict(sp=2),
                       plan=dict(ring_axis="sp", sp_impl="ulysses",
                                 sp_size=2)),
    "pp": dict(mesh=dict(pp=2), plan=dict()),
}

# Literal copy of the PRE-refactor mesh.DEFAULT_RULES regex table.
LEGACY_RULES = (
    (r".*to_qkv/kernel$", P("fsdp", None, "tp", None)),
    (r".*(to_q|to_k|to_v)/kernel$", P("fsdp", "tp")),
    (r".*ff/dense_in/kernel$", P("fsdp", "tp")),
    (r".*to_out/kernel$", P("tp", "fsdp")),
    (r".*ff/dense_out/kernel$", P("tp", "fsdp")),
    (r".*(text_emb|image_emb)/embedding$", P("fsdp", "tp")),
    (r".*to_logits_dense/(text_kernel|image_kernel)$", P("fsdp", "tp")),
    (r".*to_logits_dense/(text_bias|image_bias)$", P("tp")),
    (r".*codebook/embedding$", P(None, "fsdp")),
    (r".*/kernel$", P(None, None)),
)


@pytest.fixture(scope="module")
def tiny_trees():
    """A tiny DALLE param tree + its optimizer state (abstract — the
    sharding rules act on paths and shapes, no compute needed)."""
    from dalle_pytorch_tpu import DALLE, DALLEConfig
    from dalle_pytorch_tpu.training import make_optimizer

    cfg = DALLEConfig(dim=32, depth=2, heads=4, dim_head=8,
                      num_text_tokens=48, text_seq_len=8,
                      num_image_tokens=32, image_size=64, image_fmap_size=4)
    dalle = DALLE(cfg)
    text = jax.ShapeDtypeStruct((2, cfg.text_seq_len), jnp.int32)
    codes = jax.ShapeDtypeStruct((2, cfg.image_seq_len), jnp.int32)
    params = jax.eval_shape(dalle.init, jax.random.PRNGKey(0), text,
                            codes)["params"]
    opt = jax.eval_shape(make_optimizer(1e-3).init, params)
    return params, opt


def test_partition_rules_pin_legacy_table():
    """The plan-owned rule table (and its mesh.DEFAULT_RULES re-export)
    is pattern-for-pattern, spec-for-spec the pre-refactor table, after the
    rules for a ``TrunkSpec`` trunk's leaves (PR 27), which name none of the
    DALL-E block's."""
    assert DEFAULT_RULES is PARTITION_RULES
    assert PARTITION_RULES[:len(TRUNK_RULES)] == TRUNK_RULES
    block_rules = PARTITION_RULES[len(TRUNK_RULES):]
    assert len(block_rules) == len(LEGACY_RULES)
    for (pat, spec), (lpat, lspec) in zip(block_rules, LEGACY_RULES):
        assert pat == lpat
        assert tuple(spec) == tuple(lspec)


@pytest.mark.parametrize("name", sorted(LEGACY_PLANS))
def test_plan_generates_legacy_shardings(name, tiny_trees):
    """THE equivalence gate: plan-derived mesh kwargs, config overrides,
    and every generated sharding (params, opt state, batch) match the
    hand-kept legacy construction exactly, for all six plans."""
    plan = PLAN_REGISTRY[name]
    legacy = LEGACY_PLANS[name]
    assert plan.mesh_kwargs() == legacy["mesh"]
    assert plan.config_overrides() == legacy["plan"]

    legacy_mesh = make_mesh(**legacy["mesh"])
    legacy_pt = Partitioner(mesh=legacy_mesh, rules=LEGACY_RULES)
    pt = plan.partitioner()
    assert pt.plan is plan
    assert pt.mesh.axis_names == legacy_mesh.axis_names
    assert dict(pt.mesh.shape) == dict(legacy_mesh.shape)
    assert pt.batch_spec == legacy_pt.batch_spec
    assert pt.data_sharding == legacy_pt.data_sharding

    params, opt = tiny_trees
    for tree in (params, opt):
        got = pt.param_specs(tree)
        want = legacy_pt.param_specs(tree)
        assert jax.tree.structure(got, is_leaf=lambda x: isinstance(x, P)) \
            == jax.tree.structure(want, is_leaf=lambda x: isinstance(x, P))
        for g, w in zip(jax.tree.leaves(got,
                                        is_leaf=lambda x: isinstance(x, P)),
                        jax.tree.leaves(want,
                                        is_leaf=lambda x: isinstance(x, P))):
            assert g == w


def test_spmd_check_matrix_generated_from_registry():
    """tools/spmd_check.py no longer keeps its own plan table: its PLANS
    (mesh kwargs + DALLEConfig overrides) are generated from
    PLAN_REGISTRY minus the scale-preset rungs (presets.SCALE_PRESETS,
    whose S4 compile is a --presets / nightly concern) — and the six
    canonical plans still match the legacy pin above."""
    import importlib.util

    from dalle_pytorch_tpu.presets import SCALE_PRESETS

    spec = importlib.util.spec_from_file_location(
        "spmd_check_cli_plan_test", REPO / "tools" / "spmd_check.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    PLANS = mod.PLANS

    assert set(PLANS) == set(PLAN_REGISTRY) - set(SCALE_PRESETS)
    assert set(PLANS) == set(LEGACY_PLANS)
    assert set(SCALE_PRESETS) <= set(PLAN_REGISTRY)
    for name, spec in PLANS.items():
        assert spec["mesh"] == PLAN_REGISTRY[name].mesh_kwargs()
        assert spec["plan"] == PLAN_REGISTRY[name].config_overrides()
        assert spec == LEGACY_PLANS[name]


def test_cub512_preset_registry_and_band():
    """The cub-512 scale rung: a real PLAN_REGISTRY entry (fsdp-4, the
    ZeRO sharding that makes ~345M fit a 16 GiB chip), paired with its
    config preset, with the param count inside the declared band — the
    cheap chip-free half of the preset gate (spmd_check --presets runs
    the full S4 proof nightly)."""
    from dalle_pytorch_tpu import presets

    plan = PLAN_REGISTRY["cub-512"]
    assert plan.fsdp == 4 and plan.tp == 1 and plan.pp == 1
    assert ParallelPlan.parse("cub-512") is plan
    cfg = presets.preset_config("cub-512")
    assert cfg.dim == 512
    assert "cub-512" in presets.SCALE_PRESETS
    # band check at the tiny rung only (eval_shape at dim-512 costs
    # seconds; the cub-512 band is covered by the slow preset gate)
    assert "in band" in presets.check_param_band("tiny")
    with pytest.raises(ValueError, match="unknown preset"):
        presets.preset_config("nope")


def test_pin_update_shardings_reads_the_plan_partitioner(tiny_trees):
    """training._pin_update_shardings holds no sharding table: the specs
    it constrains to are exactly the plan partitioner's."""
    import inspect

    from dalle_pytorch_tpu import training

    src = inspect.getsource(training._pin_update_shardings)
    assert "param_shardings" in src  # derives...
    assert "PartitionSpec(" not in src  # ...and spells no specs itself


def test_plan_parse_spec_roundtrip_and_errors():
    for spec, check in [
            ("dp", lambda p: p.dp is None and p.tp == 1),
            ("dp2.tp4", lambda p: p.dp == 2 and p.tp == 4),
            ("fsdp4", lambda p: p.fsdp == 4),
            ("sp-ring2", lambda p: p.sp == 2 and p.sp_impl == "ring"),
            ("sp-ulysses2", lambda p: p.sp_impl == "ulysses"),
            ("pp2", lambda p: p.pp == 2),
            ("dcn2.fsdp2", lambda p: p.dcn_dp == 2 and p.fsdp == 2)]:
        plan = ParallelPlan.parse(spec)
        assert check(plan), spec
        assert ParallelPlan.parse(plan.spec()).spec() == plan.spec()
        rec = plan.to_manifest()
        assert ParallelPlan.from_manifest(rec).spec() == plan.spec()
    # "tp" bare IS valid (a registry name); a bare non-registry axis is not
    assert ParallelPlan.parse("tp") is PLAN_REGISTRY["tp"]
    for bad in ("xp3", "sp2", "tp2.tp4", "sp-ring2.pp2", "ep"):
        with pytest.raises(ValueError):
            ParallelPlan.parse(bad)


def test_resolve_plan_args_maps_onto_mesh_flags():
    import argparse

    ns = argparse.Namespace(plan="dp2.tp4", mesh_fsdp=1, mesh_tp=1,
                            mesh_dcn_dp=1, mesh_sp=1, sp_impl="ring",
                            pipeline_stages=1)
    plan = resolve_plan_args(ns)
    assert (ns.mesh_tp, ns.mesh_fsdp, ns.pipeline_stages) == (4, 1, 1)
    assert plan.spec() == "dp2.tp4"

    ns2 = argparse.Namespace(plan="sp-ulysses2", mesh_fsdp=1, mesh_tp=1,
                             mesh_dcn_dp=1, mesh_sp=1, sp_impl="ring",
                             pipeline_stages=1)
    resolve_plan_args(ns2)
    assert ns2.mesh_sp == 2 and ns2.sp_impl == "ulysses"

    # a trainer without an sp path refuses an sp plan loudly
    ns3 = argparse.Namespace(plan="sp-ring2", mesh_fsdp=1, mesh_tp=1,
                             mesh_dcn_dp=1)
    with pytest.raises(ValueError):
        resolve_plan_args(ns3)

    # no --plan: the legacy flags produce a faithful plan identity
    ns4 = argparse.Namespace(plan=None, mesh_fsdp=2, mesh_tp=2,
                             mesh_dcn_dp=1, mesh_sp=1, sp_impl="ring",
                             pipeline_stages=1)
    assert resolve_plan_args(ns4).spec() == "fsdp2.tp2"


def test_describe_transition():
    plan = ParallelPlan.parse("dp2.tp4")
    topo = current_topology()
    same = ParallelPlan.parse("dp2.tp4").to_manifest()
    assert describe_transition(same, plan, topo) is None
    assert describe_transition(None, plan, None) is None  # legacy manifest
    other = ParallelPlan.parse("fsdp4").to_manifest()
    note = describe_transition(other, plan, topo)
    assert "fsdp4" in note and "dp2.tp4" in note
    # same plan, different written-under device count
    wrote = dict(topo, device_count=topo["device_count"] * 2)
    assert "resharding" in describe_transition(same, plan, wrote)


def test_shard_batch_assembly_bitwise_equals_process_local_path():
    """The make_array_from_single_device_arrays assembly (SNIPPETS [2],
    the PR 8 shard_batch follow-up) is bitwise and sharding-equivalent to
    the process-local-data path it replaces, for sharded AND replicated
    batches, on every canonical mesh shape."""
    for name, plan in PLAN_REGISTRY.items():
        pt = plan.partitioner()
        x = np.arange(8 * 3, dtype=np.float32).reshape(8, 3) + len(name)
        t = np.arange(8, dtype=np.int32)
        got_x, got_t = pt.shard_batch((x, t))
        spec = P(pt.batch_axes) if pt.batch_axes else P()
        ref = jax.make_array_from_process_local_data(
            NamedSharding(pt.mesh, P(pt.batch_axes, None)), x)
        np.testing.assert_array_equal(np.asarray(got_x), np.asarray(ref))
        assert got_x.sharding.is_equivalent_to(ref.sharding, got_x.ndim), name
        np.testing.assert_array_equal(np.asarray(got_t), t)
        del spec
        # odd batch on a >1-way mesh: replicated fallback, still bitwise
        y = np.arange(3 * 2, dtype=np.float32).reshape(3, 2)
        got_y = pt.shard_batch((y,))[0]
        np.testing.assert_array_equal(np.asarray(got_y), y)
        assert got_y.sharding.is_fully_replicated


def test_manager_manifest_records_plan_and_topology(tmp_path):
    from dalle_pytorch_tpu.utils.ckpt_manager import (CheckpointManager,
                                                      latest_valid)

    plan = ParallelPlan.parse("dp2.tp4")
    mgr = CheckpointManager(tmp_path, plan=plan.to_manifest(),
                            topology=current_topology())
    mgr.save(3, {"w": np.zeros((2, 2), np.float32)})
    info = latest_valid(tmp_path)
    assert info is not None and info.step == 3
    assert info.manifest["plan"]["spec"] == "dp2.tp4"
    assert info.manifest["topology"]["device_count"] == jax.device_count()
    # the recorded plan round-trips into a usable object
    assert ParallelPlan.from_manifest(info.manifest["plan"]).tp == 4


# --- the preempt faultpoint ------------------------------------------------


def test_preempt_fires_sigterm_and_cancels_cleanly():
    from dalle_pytorch_tpu.utils import faults

    seen = []
    prev = signal.signal(signal.SIGTERM, lambda *a: seen.append(a[0]))
    try:
        faults.install("preempt:at_step=5,preempt:grace_ms=60000")
        faults.maybe_preempt(4)
        assert seen == []
        faults.maybe_preempt(5)
        assert seen == [signal.SIGTERM]
        assert faults.get_registry().config("preempt", "grace_ms") == 60000
        # fires once
        faults.maybe_preempt(5)
        assert seen == [signal.SIGTERM]
    finally:
        faults.cancel_preempt_grace()
        faults.reset()
        signal.signal(signal.SIGTERM, prev)
    assert faults._preempt_timers == []


def test_preempt_grace_ms_grammar_rejects_junk():
    from dalle_pytorch_tpu.utils import faults

    with pytest.raises(ValueError):
        faults.FaultRegistry("preempt:grace=bad")
    reg = faults.FaultRegistry("preempt:grace_ms=250")
    assert reg.config("preempt", "grace_ms") == 250
    assert reg.config("preempt", "at_step") is None
    # grace_ms alone never fires anything
    assert reg.fire("preempt", step=250) == frozenset()


def test_preempt_grace_expiry_hard_exits_74():
    """Subprocess drill: a process that IGNORES the preemption notice
    (SIGTERM blocked — the stuck-in-a-device-call shape) is hard-killed
    with ExitCode.PREEMPT_EXPIRED when the grace window closes, exactly
    like the scheduler's follow-up SIGKILL."""
    code = r"""
import signal, sys, time
sys.path.insert(0, {repo!r})
signal.signal(signal.SIGTERM, signal.SIG_IGN)  # the wedged trainer
from dalle_pytorch_tpu.utils import faults
faults.install("preempt:at_step=1,preempt:grace_ms=300")
faults.maybe_preempt(1)
time.sleep(30)  # the grace timer must end this long before 30s
print("survived", flush=True)
""".format(repo=str(REPO))
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=25)
    assert proc.returncode == 74, (proc.returncode, proc.stdout,
                                   proc.stderr)
    assert "grace window" in proc.stderr
    assert "survived" not in proc.stdout


def test_monitor_restart_plan_appends_flag(tmp_path):
    """monitor --restart-plan: the elastic relaunch appends --plan SPEC
    (or substitutes {plan}) so a preempted run comes back on the topology
    the operator names."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "monitor_cli_plan_test", REPO / "tools" / "monitor.py")
    monitor = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(monitor)

    hb = tmp_path / "hb"
    hb.mkdir()
    (hb / "heartbeat-p0.json").write_text('{"step": 3, "time": 1}')
    marker = tmp_path / "ran.txt"
    ckpts = tmp_path / "ckpts"
    from dalle_pytorch_tpu.utils.ckpt_manager import CheckpointManager

    CheckpointManager(ckpts).save(3, {"w": np.zeros((2,), np.float32)})
    code = monitor.main([str(hb), "--timeout", "1",
                         "--ckpt-dir", str(ckpts),
                         "--restart-plan", "dp2.tp4",
                         "--restart-cmd",
                         f"echo relaunch > {marker}; echo"])
    assert code == 1  # still stalled after the restart attempt
    # the spawned command got the plan flag appended
    assert marker.exists()
    sub = tmp_path / "sub.txt"
    monitor.main([str(hb), "--timeout", "1", "--ckpt-dir", str(ckpts),
                  "--restart-plan", "fsdp4",
                  "--restart-cmd", f"echo plan={{plan}} > {sub}"])
    assert sub.read_text().strip() == "plan=fsdp4"
