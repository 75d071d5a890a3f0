"""graftspmd tests: each analysis S1-S4 catches its deliberately-broken
fixture (the teeth-proof, mirroring test_contract_check.py), the clean
twins pass, the factory-coverage gate keeps training.STEP_FACTORIES and
the CLI harness in sync, and the CLI's quick full pass stays green on the
clean tree (slow tier — it compiles every plan)."""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dalle_pytorch_tpu.lint import spmd  # noqa: E402
from dalle_pytorch_tpu.lint import spmd_fixtures as fx  # noqa: E402
from dalle_pytorch_tpu.parallel.mesh import make_mesh  # noqa: E402
from dalle_pytorch_tpu.training import STEP_FACTORIES, make_optimizer  # noqa: E402


def _load_cli():
    spec = importlib.util.spec_from_file_location(
        "spmd_check_cli", REPO / "tools" / "spmd_check.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cli():
    return _load_cli()


# --- S1: collective order -------------------------------------------------


def test_s1_conditional_collective_caught():
    mesh = make_mesh()
    x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
    jaxpr = jax.make_jaxpr(fx.make_conditional_collective_step(mesh))(x)
    with pytest.raises(spmd.SPMDViolation, match="S1 collective order"):
        spmd.check_collective_order(jaxpr)


def test_s1_branch_matched_cond_passes():
    """Identical collective sequences on every branch keep shards in
    lockstep (the pipeline drain-bubble pattern) — no violation, and the
    branch collectives count toward the unconditional sequence."""
    mesh = make_mesh()
    x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
    jaxpr = jax.make_jaxpr(fx.make_branch_matched_collective_step(mesh))(x)
    sites = spmd.check_collective_order(jaxpr)
    assert [s.prim for s in sites] == ["ppermute"]


def test_s1_collective_in_while_body_caught():
    """A collective under a data-dependent trip count deadlocks shards
    that disagree on the iteration count."""
    mesh = make_mesh()

    def local(x):
        def body(v):
            return jax.lax.psum(v, "dp") * 0.5

        return jax.lax.while_loop(lambda v: jnp.sum(v) > 1.0, body, x)

    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    fn = shard_map(local, mesh=mesh, in_specs=(P("dp"),),
                   out_specs=P("dp"), check_vma=False)
    jaxpr = jax.make_jaxpr(fn)(jnp.ones((8, 4), jnp.float32))
    with pytest.raises(spmd.SPMDViolation, match="while"):
        spmd.check_collective_order(jaxpr)


def test_s1_recurses_into_scan_bodies():
    """Collectives inside scan (static trip count) are uniform across
    shards — recorded, not flagged."""
    mesh = make_mesh()

    def local(x):
        def body(carry, row):
            return carry + jax.lax.psum(row, "dp"), None

        out, _ = jax.lax.scan(body, jnp.zeros_like(x[0]), x)
        return out

    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    fn = shard_map(local, mesh=mesh, in_specs=(P(None, "dp"),),
                   out_specs=P("dp"), check_vma=False)
    jaxpr = jax.make_jaxpr(fn)(jnp.ones((4, 8), jnp.float32))
    sites = spmd.check_collective_order(jaxpr)
    assert [s.prim for s in sites] == ["psum"]
    assert any("scan" in c for c in sites[0].context)


# --- S1 extension: scan collective schedules (the pp microbatch gate) -----


def test_scan_schedule_extracts_length_times_sequence():
    """The clean GPipe-shaped scan: the schedule is a static
    ``length x [ppermute]`` fact, with the total derivable."""
    mesh = make_mesh()
    x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
    scheds = spmd.scan_collective_schedule(
        jax.make_jaxpr(fx.make_pipelined_collective_scan(mesh, length=5))(x))
    assert len(scheds) == 1
    s = scheds[0]
    assert s.length == 5
    assert [sig[0] for sig in s.per_iteration] == ["ppermute"]
    assert s.total == 5
    assert "5 iterations x [ppermute]" in s.format()


def test_scan_schedule_refuses_unbalanced_microbatch_scan():
    """The epilogue-folded-into-the-last-iteration anti-pattern: a cond
    inside the scan body whose branches issue DIFFERENT collective
    sequences means no static iteration-count x sequence schedule exists
    — refused, not mis-summarized."""
    mesh = make_mesh()
    x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
    with pytest.raises(spmd.SPMDViolation, match="scan schedule"):
        spmd.scan_collective_schedule(
            jax.make_jaxpr(fx.make_unbalanced_microbatch_scan(mesh))(x))


def test_pp_scan_schedule_check_passes_and_reports(cli):
    """The production pp step's microbatch scan obeys the law: trip count
    = microbatches + stages - 1, per-iteration collective sequence
    IDENTICAL across microbatch counts (forward and transposed backward
    scans both)."""
    detail = cli.pp_scan_schedule_check()
    assert "(m + pp - 1) x fixed sequence" in detail
    assert "m=2: 3 iterations" in detail and "m=4: 5 iterations" in detail


# --- S2: donation audit ---------------------------------------------------


def _undonated_lowered():
    tx = make_optimizer(1e-3)
    params = fx.fixture_params()
    opt = tx.init(params)
    step = fx.make_undonated_train_step(tx)
    return step.lower(params, opt, jnp.ones((8, 64), jnp.float32))


def test_s2_dropped_donation_caught():
    with pytest.raises(spmd.SPMDViolation, match="NOT donated"):
        spmd.check_donation(_undonated_lowered(),
                            ("params", "opt_state", "batch"), (0, 1))


def test_s2_audit_reports_undonated_leaves():
    audit = spmd.audit_donation(_undonated_lowered(),
                                ("params", "opt_state", "batch"), (0, 1))
    assert audit.donated_bytes == 0
    assert len(audit.missing) == 9  # w/b + adam mu/nu/count per leaf...
    assert not audit.ok()


def test_s2_donating_twin_passes():
    import optax

    tx = make_optimizer(1e-3)
    params = fx.fixture_params()
    opt = tx.init(params)

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            return jnp.mean((batch @ p["w"] + p["b"]) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step = jax.jit(train_step, donate_argnums=(0, 1))
    lowered = step.lower(params, opt, jnp.ones((8, 64), jnp.float32))
    with spmd.fresh_stats_compile():
        compiled = lowered.compile()
    audit = spmd.check_donation(lowered, ("params", "opt_state", "batch"),
                                (0, 1), compiled=compiled)
    assert audit.missing == []
    assert audit.donated_bytes > 0
    assert audit.donated_leaves > 0
    assert audit.aliased_params >= audit.donated_leaves


def test_s2_alias_free_executable_is_caught():
    """Donation requested at the jax level but absent from the compiled
    HLO's input_output_alias config = the compiler silently dropped it —
    a loud failure, not a silent donation pass."""
    import optax

    tx = make_optimizer(1e-3)
    params = fx.fixture_params()
    opt = tx.init(params)

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            return jnp.mean((batch @ p["w"] + p["b"]) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step = jax.jit(train_step, donate_argnums=(0, 1))
    lowered = step.lower(params, opt, jnp.ones((8, 64), jnp.float32))

    class FakeCompiled:
        def as_text(self):
            return "HloModule jit_train_step\nENTRY %main () -> f32[] {}"

    with pytest.raises(spmd.SPMDViolation, match="aliases only 0"):
        spmd.check_donation(lowered, ("params", "opt_state", "batch"),
                            (0, 1), compiled=FakeCompiled())


def test_s2_alias_count_parses_hlo_config():
    """compiled_alias_count reads the real optimized-HLO alias config —
    nested tuple indices and multiple params counted distinctly."""

    class FakeCompiled:
        def as_text(self):
            return ("ENTRY %main (p0: f32[4], p1: f32[4]) -> (f32[4], "
                    "f32[4]), input_output_alias={ {0}: (0, {}, "
                    "may-alias), {1}: (1, {}, may-alias) } {")

    assert spmd.compiled_alias_count(FakeCompiled()) == 2

    class NoAlias:
        def as_text(self):
            return "ENTRY %main () -> f32[] {}"

    assert spmd.compiled_alias_count(NoAlias()) == 0


# --- S3: retrace sentinel -------------------------------------------------


def test_s3_weak_hash_static_arg_caught():
    jitted, make_args = fx.make_retracing_step()
    with pytest.raises(spmd.SPMDViolation, match="traces"):
        spmd.check_single_trace(jitted, make_args, steps=3)


def test_s3_unhashable_static_arg_caught():
    jitted, make_args = fx.make_unhashable_static_step()
    with pytest.raises(spmd.SPMDViolation, match="hash"):
        spmd.check_single_trace(jitted, make_args, steps=3)


def test_s3_stable_step_traces_once():
    jitted, make_args = fx.make_stable_step()
    assert spmd.count_traces(jitted, make_args, steps=4) == 1


# --- S3 (serve): the continuous-batching tick -----------------------------


def test_s3_shape_changing_serve_tick_caught():
    """The occupancy-cropped tick recompiles per admit/retire — the storm
    the serve arena's fixed shapes exist to prevent."""
    jitted, make_args = fx.make_shape_changing_serve_tick()
    with pytest.raises(spmd.SPMDViolation, match="traces"):
        spmd.check_single_trace(jitted, make_args, steps=4,
                                label="serve-fixture")


def test_s3_serve_harness_clean_on_real_arena(cli):
    """The CLI's serve-tick harness: real GenerationServer, admit/retire
    churn across occupancies + a clock wrap, one executable per entry
    point."""
    detail = cli.serve_retrace_check()
    assert "compiled once" in detail


# --- S4 opt0-drift gate (scheduled CI) ------------------------------------


def test_s4_drift_gate_clean_at_tiny_geometry(cli):
    detail = cli.s4_drift_check(make_cfg=cli.tiny_config)
    assert "opt0 == full-opt" in detail


def test_s4_drift_gate_catches_divergence(cli, monkeypatch):
    """A synthetic opt0/full-opt disagreement (the XLA-upgrade failure
    mode the scheduled job watches for) must raise."""
    import dataclasses as dc

    estimates = iter([
        spmd.HBMEstimate(argument_bytes=100, output_bytes=50,
                         alias_bytes=0, temp_bytes=1000),       # full-opt
        spmd.HBMEstimate(argument_bytes=100, output_bytes=50,
                         alias_bytes=0, temp_bytes=400),        # opt0
    ])
    monkeypatch.setattr(cli.spmd, "hbm_estimate",
                        lambda compiled: next(estimates))

    class _FakeLowered:
        def compile(self, *a, **k):
            return object()

    monkeypatch.setattr(cli, "dalle_step_lowered",
                        lambda *a, **k: _FakeLowered())
    with pytest.raises(spmd.SPMDViolation, match="temp_bytes"):
        cli.s4_drift_check()


# --- S4: static HBM budget ------------------------------------------------


@pytest.fixture(scope="module")
def oversized_estimate():
    return spmd.hbm_estimate(fx.oversized_step_compiled())


def test_s4_oversized_plan_caught(oversized_estimate, monkeypatch):
    monkeypatch.setitem(spmd.CHIP_HBM_BYTES, "toy-1mib", 1 << 20)
    with pytest.raises(spmd.SPMDViolation, match="OOMs at step 0"):
        spmd.check_hbm_budget(oversized_estimate, "toy-1mib")


def test_s4_fitting_plan_passes(oversized_estimate, monkeypatch):
    monkeypatch.setitem(spmd.CHIP_HBM_BYTES, "toy-1gib", 1 << 30)
    spmd.check_hbm_budget(oversized_estimate, "toy-1gib")
    # real chips fit the toy program trivially
    spmd.check_hbm_budget(oversized_estimate, "v4-8")
    spmd.check_hbm_budget(oversized_estimate, "cpu-virtual")


def test_s4_unknown_chip_is_an_error(oversized_estimate):
    with pytest.raises(spmd.SPMDViolation, match="unknown chip"):
        spmd.check_hbm_budget(oversized_estimate, "v9-512")


def test_s4_estimate_subtracts_donated_aliases():
    est = spmd.HBMEstimate(argument_bytes=100, output_bytes=100,
                           alias_bytes=80, temp_bytes=30)
    assert est.total_bytes == 150


# --- the CLI harness ------------------------------------------------------


def test_factory_coverage_gate(cli):
    """training.STEP_FACTORIES and the CLI harness agree — and the gate
    fires when they drift."""
    cli.check_factory_coverage()
    assert set(cli.HARNESSED_FACTORIES) == set(STEP_FACTORIES)
    try:
        STEP_FACTORIES["brand_new"] = lambda: None
        with pytest.raises(spmd.SPMDViolation, match="coverage drift"):
            cli.check_factory_coverage()
    finally:
        STEP_FACTORIES.pop("brand_new", None)


def test_cli_plans_match_contract_check(cli):
    assert set(cli.PLANS) == {"dp", "fsdp", "tp", "sp-ring", "sp-ulysses",
                              "pp"}


def test_decode_path_is_collective_free_today(cli):
    """The decode scan carries no collectives at the current plans — S1
    pins that a future sharded sampler cannot slip a conditional one in
    silently."""
    sites = spmd.check_collective_order(cli.decode_jaxpr(), label="decode")
    assert sites == []


@pytest.mark.slow
def test_cli_quick_full_pass_and_selftest(cli, tmp_path):
    """The end-to-end gate: the clean tree passes every analysis on every
    plan (tiny geometry), the JSON artifact is well-formed, and the
    selftest proves each analysis catches its fixture."""
    out = tmp_path / "spmd.json"
    assert cli.run_all(chip="v4-8", quick=True, json_out=str(out)) == 0
    doc = json.loads(out.read_text())
    assert doc["failures"] == 0
    assert {r["analysis"] for r in doc["results"]} >= {
        "S1-collectives", "S2-donation", "S3-retrace", "S4-hbm"}
    statuses = {r["status"] for r in doc["results"]}
    assert statuses == {"PASS"}
    assert cli.selftest() == 0
