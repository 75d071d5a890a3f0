"""Run-report aggregation over a telemetry stream (tools/obs_report.py).

Turns the raw event stream into the answers an operator actually asks
after (or during) a run: where did the time go (step-time/MFU/stall
trajectory + the StepTimer reservoir percentiles), was it healthy (verdict
timeline, rollbacks, watchdog fires), did the checkpoints keep up (publish
cadence, save durations, fallbacks), how did serving do (p50/p99 latency
per SLO class, attainment, preemptions), and what was injected or broke
(fault + quarantine events).  Stdlib-only, like the rest of ``obs`` — it
must run on the box whose accelerator just wedged.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional

from . import compiles


def _pct(values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile, stdlib-only (no numpy on the read side)."""
    if not values:
        return None
    ordered = sorted(values)
    idx = min(int(round((q / 100.0) * (len(ordered) - 1))), len(ordered) - 1)
    return float(ordered[idx])


def _span_pairs(events: List[dict]) -> List[dict]:
    """Matched span pairs as merged dicts (B fields + dur_s/ok from E)."""
    begins = {(r.get("host", 0), r.get("seq")): r
              for r in events if r.get("ph") == "B"}
    out = []
    for r in events:
        if r.get("ph") != "E":
            continue
        b = begins.pop((r.get("host", 0), r.get("sid")), None)
        if b is not None:
            merged = dict(b)
            merged.update(dur_s=r.get("dur_s"), ok=r.get("ok", True))
            out.append(merged)
    # whatever stayed in `begins` is a torn span (death inside it)
    out.sort(key=lambda r: (r.get("host", 0), r.get("seq", 0)))
    return out


def _torn_spans(events: List[dict]) -> List[dict]:
    ended = {(r.get("host", 0), r.get("sid")) for r in events
             if r.get("ph") == "E"}
    return [r for r in events if r.get("ph") == "B"
            and (r.get("host", 0), r.get("seq")) not in ended]


#: the spans GenerationServer.step writes inside one `serve.step`
#: (serve/scheduler.py); `prefill` nests inside `admit`, the rest are the
#: step's own children
SERVE_PHASES = ("retire", "admit", "prefill", "tick")
_SERVE_STEP_CHILDREN = ("retire", "admit", "tick", "mem_watermark")


def _serve_phases(serve: List[dict]) -> Optional[dict]:
    """Where the recorded scheduler iterations' time went: per phase its
    count, total and median seconds and share of `serve.step` time, and the
    steps' self time (a span's duration less what its children cover).
    Only spans inside a recorded step count, so a sampled stream
    (`tick_sample` > 1: one step in N written, every retire and admit)
    still reads true shares.  None when the stream holds no step span."""
    spans = [r for r in _span_pairs(serve)
             if r.get("dur_s") is not None and r.get("mono") is not None]
    steps = [r for r in spans if r.get("name") == "step"]
    if not steps:
        return None
    def lane_of(r) -> tuple:
        return (r.get("run"), r.get("host", 0), r.get("pid"), r.get("thread"))

    by_lane: Dict[tuple, list] = {}
    for r in steps:
        by_lane.setdefault(lane_of(r), []).append(
            (float(r["mono"]), float(r["mono"]) + float(r["dur_s"])))
    for lane in by_lane.values():
        lane.sort()

    def in_a_step(r) -> bool:
        lane = by_lane.get(lane_of(r), [])
        k = bisect.bisect_right(lane, (float(r["mono"]), float("inf"))) - 1
        return k >= 0 and float(r["mono"]) <= lane[k][1]

    step_s = sum(float(r["dur_s"]) for r in steps)
    inside = [r for r in spans if r.get("name") != "step" and in_a_step(r)]
    phases = {}
    for name in SERVE_PHASES:
        durs = [float(r["dur_s"]) for r in inside if r.get("name") == name]
        phases[name] = {"count": len(durs), "total_s": sum(durs),
                        "median_s": _pct(durs, 50),
                        "share": sum(durs) / step_s if step_s else None}
    self_s = max(step_s - sum(float(r["dur_s"]) for r in inside
                              if r.get("name") in _SERVE_STEP_CHILDREN), 0.0)
    return {"steps": len(steps), "step_s": step_s,
            "step_median_s": _pct([float(r["dur_s"]) for r in steps], 50),
            "self_s": self_s,
            "self_share": self_s / step_s if step_s else None,
            "phases": phases}


def build_report(events: List[dict]) -> dict:
    """Aggregate parsed records (telemetry.read_events output) into the
    run-report dict ``render_text`` prints and ``--format json`` emits."""
    by_kind: Dict[str, int] = {}
    for r in events:
        by_kind[r.get("kind", "?")] = by_kind.get(r.get("kind", "?"), 0) + 1

    runs: Dict[str, dict] = {}
    for r in events:
        run = runs.setdefault(str(r.get("run", "?")), {
            "hosts": set(), "t_first": None, "t_last": None, "records": 0})
        run["hosts"].add(r.get("host", 0))
        run["records"] += 1
        t = r.get("t")
        if t is not None:
            run["t_first"] = t if run["t_first"] is None \
                else min(run["t_first"], t)
            run["t_last"] = t if run["t_last"] is None \
                else max(run["t_last"], t)
    for run in runs.values():
        run["hosts"] = sorted(run["hosts"])
        run["wall_s"] = (run["t_last"] - run["t_first"]
                         if run["t_first"] is not None else None)

    # --- steps ------------------------------------------------------------
    steps = [r for r in events if r.get("kind") == "step" and "ph" not in r]
    losses = [float(r["loss"]) for r in steps if r.get("loss") is not None]
    step_report: dict = {"records": len(steps)}
    if steps:
        sids = [int(r["step"]) for r in steps if r.get("step") is not None]
        step_report.update(
            first_step=min(sids) if sids else None,
            last_step=max(sids) if sids else None,
            loss_first=losses[0] if losses else None,
            loss_last=losses[-1] if losses else None,
            loss_min=min(losses) if losses else None,
            step_time_p50=_pct([float(r["step_time_s"]) for r in steps
                                if r.get("step_time_s") is not None], 50),
            mfu_last=next((float(r["mfu"]) for r in reversed(steps)
                           if r.get("mfu") is not None), None),
            stall_frac_mean=(lambda v: sum(v) / len(v) if v else None)(
                [float(r["loader_stall_frac"]) for r in steps
                 if r.get("loader_stall_frac") is not None]))
    # the StepTimer reservoir percentiles ride run_end / perf_summary events
    perf = [r for r in events if r.get("name") in ("perf_summary", "run_end")
            and r.get("step_time_p50") is not None]
    if perf:
        step_report["reservoir"] = {
            k: perf[-1].get(k) for k in ("step_time_p50", "step_time_p99",
                                         "stall_p50", "stall_p99",
                                         "reservoir_n")
            if perf[-1].get(k) is not None}

    # --- health -----------------------------------------------------------
    health = [r for r in events if r.get("kind") == "health"]
    verdicts: Dict[str, int] = {}
    for r in health:
        verdicts[r.get("name", "?")] = verdicts.get(r.get("name", "?"), 0) + 1
    health_report = {
        "verdicts": verdicts,
        "timeline": [{"step": r.get("step"), "name": r.get("name"),
                      "loss": r.get("loss"), "host": r.get("host", 0)}
                     for r in health
                     if r.get("name") not in ("ok",)][:50],
    }

    # --- checkpoints --------------------------------------------------------
    ckpt = [r for r in events if r.get("kind") == "ckpt"]
    publishes = [r for r in ckpt if r.get("name") == "publish"]
    pub_steps = sorted(int(r["step"]) for r in publishes
                       if r.get("step") is not None)
    pub_times = sorted(float(r["t"]) for r in publishes if r.get("t"))
    save_spans = [r for r in _span_pairs(ckpt) if r.get("name") == "save"]
    ckpt_report = {
        "publishes": len(publishes),
        "publish_steps": pub_steps[-20:],
        "cadence_s": ((pub_times[-1] - pub_times[0]) / (len(pub_times) - 1)
                      if len(pub_times) > 1 else None),
        "save_dur_p50": _pct([float(r["dur_s"]) for r in save_spans
                              if r.get("dur_s") is not None], 50),
        "save_dur_max": max((float(r["dur_s"]) for r in save_spans
                             if r.get("dur_s") is not None), default=None),
        "fallback_skips": sum(r.get("name") == "fallback_skip" for r in ckpt),
        "failed_saves": sum(r.get("name") == "save_failed" for r in ckpt),
        "torn_saves": len([r for r in _torn_spans(ckpt)
                           if r.get("name") == "save"]),
    }

    # --- serve --------------------------------------------------------------
    # `serve.retire` / `admit` / `tick` name a span (ph B/E: where the time
    # went, _serve_phases) and an event (what happened) alike
    serve_all = [r for r in events if r.get("kind") == "serve"]
    serve = [r for r in serve_all if "ph" not in r]
    retires = [r for r in serve if r.get("name") == "retire"]
    classes = sorted({str(r.get("slo")) for r in retires}) or []
    per_class = {}
    for slo in classes:
        rows = [r for r in retires if str(r.get("slo")) == slo]
        lat = [float(r["latency_s"]) for r in rows
               if r.get("latency_s") is not None]
        waits = [float(r["queue_wait_s"]) for r in rows
                 if r.get("queue_wait_s") is not None]
        judged = [r for r in rows if r.get("slo_ok") is not None]
        per_class[slo] = {
            "completed": len(rows),
            "latency_p50": _pct(lat, 50), "latency_p99": _pct(lat, 99),
            "queue_wait_mean": sum(waits) / len(waits) if waits else None,
            "attainment": (sum(bool(r["slo_ok"]) for r in judged)
                           / len(judged) if judged else None),
        }
    # tick records may be SAMPLED aggregates (GenerationServer
    # tick_sample > 1): each carries `ticks` = how many decode ticks it
    # covers (absent = the legacy 1:1 record) and `active_sum` = the
    # occupied-slot-ticks of the window — sum those, never count records
    ticks = [r for r in serve if r.get("name") == "tick"]
    covered = sum(int(r.get("ticks", 1)) for r in ticks)
    slot_ticks = sum(
        int(r["active_sum"]) if r.get("active_sum") is not None
        else int(r.get("active", 0)) * int(r.get("ticks", 1))
        for r in ticks)
    # prefix cache: one `prefix` record per admission (hit flag +
    # RUNNING totals) — counts sum, totals read off the LAST record
    prefix_recs = [r for r in serve if r.get("name") == "prefix"]
    prefix_report = None
    if prefix_recs:
        hits = sum(bool(r.get("hit")) for r in prefix_recs)
        prefix_report = {
            "lookups": len(prefix_recs),
            "hits": hits,
            "hit_rate": hits / len(prefix_recs),
            "entries": prefix_recs[-1].get("entries"),
            "prefill_flops_saved": prefix_recs[-1].get("flops_saved"),
        }
    # serve/engine.py::SlotArena emits one `serve.arena_layout` record per
    # built arena: the form it stores its caches in and the cache-sized
    # copies left in its compiled tick; the last arena speaks
    arenas = [r for r in serve if r.get("name") == "arena_layout"]
    arena_report = ({"arenas": len(arenas),
                     **{k: arenas[-1].get(k) for k in (
                         "slots", "folded_layers", "plain_layers",
                         "ring_layers", "recurrent_layers",
                         "install_bytes_per_slot", "tick_relayout_bytes")}}
                    if arenas else None)
    serve_report = {
        "arena": arena_report,
        "submitted": sum(r.get("name") == "submit" for r in serve),
        "completed": len(retires),
        "failed": sum(r.get("name") == "fail" for r in serve),
        "preemptions": sum(r.get("name") == "preempt" for r in serve),
        "ticks": covered,
        "tick_records": len(ticks),
        "occupied_slot_ticks": slot_ticks,
        "decoded_tokens": sum(int(r.get("tokens", 0)) for r in retires),
        "prefix": prefix_report,
        "by_class": per_class,
        "phases": _serve_phases(serve_all),
    }

    # --- roofline: predicted vs measured ------------------------------------
    # trainers emit one `prof.predicted` record at run start (the perf
    # ledger's roofline ceiling for their config fingerprint); joined here
    # with the StepTimer's measured MFU it answers "is this run as fast as
    # this code CAN go" rather than "as fast as it used to go"
    prof_rows = [r for r in events if r.get("kind") == "prof"
                 and r.get("name") == "predicted" and "ph" not in r]
    prof_report: Optional[dict] = None
    if prof_rows:
        p = prof_rows[-1]
        measured = step_report.get("mfu_last")
        predicted = p.get("mfu")
        prof_report = {
            "fingerprint": p.get("fingerprint"),
            "exact": p.get("exact"),
            "chip": p.get("chip"),
            "predicted_mfu": predicted,
            "pred_step_time_s": p.get("pred_step_time_s"),
            "bound": p.get("bound"),
            "measured_mfu": measured,
            "measured_step_time_p50": step_report.get("step_time_p50"),
            "attained_frac": (float(measured) / float(predicted)
                              if measured is not None and predicted
                              else None),
        }

    # --- compiles -----------------------------------------------------------
    # obs/compiles.py forwards jax's trace / lower / compile-or-load / cache
    # events as `compile` records; a trace AFTER the first step record is a
    # retrace inside the run's steady state (a new shape, a rebuilt jit)
    comp = [r for r in events if r.get("kind") == "compile"]
    compile_report: Optional[dict] = None
    if comp:
        summary = compiles.summarize(
            {"phase": str(r.get("name", "?")), "fun_name": r.get("fun"),
             "t": 0.0, "dur_s": r.get("dur_s")} for r in comp)

        def stream(r):
            return (str(r.get("run", "?")), r.get("host", 0))

        first_step: Dict[tuple, int] = {}
        for r in steps:
            first_step[stream(r)] = min(r.get("seq", 0),
                                        first_step.get(stream(r), 1 << 62))
        compile_report = {
            "phases": {p: row for p, row in summary["phases"].items()
                       if row["count"]},
            "top": [{"fun": f["fun_name"], "seconds": f["seconds"]}
                    for f in summary["top"][:5]],
            "traces_after_first_step": sum(
                r.get("name") == "trace"
                and r.get("seq", 0) > first_step.get(stream(r), 1 << 62)
                for r in comp),
        }

    # --- decode: the static sampler's cache layout ---------------------------
    # models/dalle.py::decode_codes emits one `decode.kv_layout` record per
    # trace: how many layers' KV caches its scan carries head-folded
    # (lane-dense) and how many in the plain layout; and one
    # `decode.state_layout` record: how many layers carry keys and values,
    # how many a recurrent state, the bytes a row holds; the last trace speaks
    def last_decode(name, keys):
        found = [r for r in events
                 if r.get("kind") == "decode" and r.get("name") == name]
        return len(found), ({k: found[-1].get(k) for k in keys}
                            if found else {})

    traces, kv = last_decode(
        "kv_layout", ("rows", "kv_lane_dense_layers", "kv_plain_layers"))
    _, state = last_decode(
        "state_layout", ("kv_layers", "ssm_layers", "state_bytes_per_row"))
    # where some of the recurrent layers are linear attention, how many, the
    # shape a row of their matrix state is carried in and how many of those
    # states the tick updates in one pass
    _, linear = last_decode(
        "state_layout", ("linear_layers", "linear_state_shape",
                         "linear_one_pass_layers"))
    # where some layers cache a latent in place of keys and values, how many,
    # the bytes a position holds and the bytes its stored form walks
    _, latent = last_decode(
        "state_layout", ("latent_layers", "latent_bytes_per_position",
                         "latent_bytes_walked_per_position"))
    # over a routed trunk a `decode.moe_layout` record besides: the expert
    # layers, their banks' bytes, the window layers and the key slots a row
    # holds over all layers
    _, routed = last_decode(
        "moe_layout", ("layers", "experts", "experts_per_token",
                       "expert_bytes_per_layer", "window_layers",
                       "kv_slots_per_row"))
    # and a `decode.kv_reach` record: the layers whose dense cache read the
    # position bounds, and the share of their slots a tick reads
    # a shared-expert layer says how it scores, how many of its experts this
    # device holds and how many shared experts stand beside them
    _, share = last_decode(
        "moe_layout", ("scoring", "experts_held", "shared_experts"))
    _, reach = last_decode(
        "kv_reach", ("bounded_layers", "unbounded_layers", "buckets",
                     "read_share"))
    decode_report: Optional[dict] = None
    if traces:
        decode_report = {"traces": traces, **kv, **state,
                         **({"reach": reach} if reach else {}),
                         **({"linear": linear}
                            if linear.get("linear_layers") else {}),
                         **({"latent": latent}
                            if latent.get("latent_layers") else {}),
                         **({"moe": routed} if routed else {}),
                         **({"moe_share": share}
                            if share.get("shared_experts") else {})}
    # models/dalle.py::sample_image_code emits one `sample.top_k` record per
    # traced sampler (a decode_codes program holds two, a serve tick its
    # own): how many logits the top-k filter keeps and how it finds the
    # cut-off; the last one speaks
    top_k = [r for r in events
             if r.get("kind") == "sample" and r.get("name") == "top_k"]
    sampler_report: Optional[dict] = None
    if top_k:
        sampler_report = {"traces": len(top_k), **{
            k: top_k[-1].get(k)
            for k in ("rows", "vocab", "k", "passes", "method")}}

    # --- attention: which core the model's layers run ------------------------
    # ops/attention.py::record_kernel_choices emits one `attention.kernel`
    # record per trace of a model: layers on the flash kernel, layers on the
    # dense-masked branch, the share of blocks the flash layers compute, and
    # the kernel's operand contract (heads a program, padding it adds in HBM)
    # and the pallas_calls a flash layer's backward makes
    kernel = [r for r in events if r.get("kind") == "attention"
              and r.get("name") == "kernel"]
    attention_report: Optional[dict] = None
    if kernel:
        attention_report = {"traces": len(kernel), **{
            k: kernel[-1].get(k) for k in
            ("model", "n", "tiles", "flash_layers", "dense_layers",
             "blocks_computed_share", "heads_per_program", "hbm_pad_rows",
             "backward_calls")}}
        # latent layers say how a tick of theirs reads the cache; the line
        # stands under `-- decode --`, beside the latent cache's
        if kernel[-1].get("latent_read") and "latent" in (decode_report or {}):
            decode_report["latent"].update(
                {k: kernel[-1].get(k) for k in ("latent_read", "block")})

    # --- memory: predicted vs measured --------------------------------------
    # MemTracker emits `mem.watermark` at phase boundaries (obs/mem.py)
    # and trainers emit one `mem.predicted` record (the ledger's memory
    # timeline for their fingerprint); the join answers "is this run's
    # HBM where the ledger says it should be, and how close to the edge"
    marks = [r for r in events if r.get("kind") == "mem"
             and r.get("name") == "watermark" and "ph" not in r]
    mem_pred = [r for r in events if r.get("kind") == "mem"
                and r.get("name") == "predicted" and "ph" not in r]
    mem_report: Optional[dict] = None
    if marks or mem_pred:
        by_phase: dict = {}
        for r in marks:  # last watermark per phase wins
            by_phase[str(r.get("phase", "?"))] = {
                k: r.get(k) for k in
                ("live_count", "live_bytes", "used_bytes", "peak_bytes",
                 "rss_bytes", "headroom_bytes", "headroom_frac")
                if r.get(k) is not None}
        leaks = [r for r in events if r.get("kind") == "mem"
                 and r.get("name") == "leak_check" and "ph" not in r]
        mem_report = {
            "watermarks": by_phase,
            "peak_bytes": max((int(r.get("peak_bytes", 0)) for r in marks),
                              default=None),
            "headroom_frac_min": min(
                (float(r["headroom_frac"]) for r in marks
                 if r.get("headroom_frac") is not None), default=None),
            "predicted": ({k: mem_pred[-1].get(k) for k in
                           ("fingerprint", "exact", "chip", "phases",
                            "peak_phase", "peak_bytes", "headroom_frac",
                            "fits")} if mem_pred else None),
            "leak_checks": {"total": len(leaks),
                            "failed": sum(not r.get("ok", True)
                                          for r in leaks)},
        }

    # --- faults / data ------------------------------------------------------
    faults = [{"site": r.get("name"), "action": r.get("action"),
               "step": r.get("step"), "hits": r.get("hits"),
               "host": r.get("host", 0)}
              for r in events if r.get("kind") == "fault"][:50]
    data = [r for r in events if r.get("kind") == "data"]
    data_report = {
        "sample_quarantines": sum(r.get("name") == "sample_quarantine"
                                  for r in data),
        "shard_quarantines": sum(r.get("name") == "shard_quarantine"
                                 for r in data),
        "loader_stalls": sum(r.get("name") == "loader_stall" for r in data),
    }

    # --- locks (graftrace witness) ------------------------------------------
    # one kind="lock" event per lock name (locks.emit_telemetry), plus one
    # "order_graph" verdict event; last record per (host, name) wins — the
    # stats are cumulative counters, not deltas
    lock_events = [r for r in events if r.get("kind") == "lock"]
    per_lock: Dict[tuple, dict] = {}
    graph = None
    for r in lock_events:
        if r.get("name") == "order_graph":
            graph = r
        else:
            per_lock[(r.get("host", 0), r.get("name", "?"))] = r
    lock_rows = sorted(
        ({"name": name, "host": host,
          "acquires": int(r.get("acquires", 0)),
          "contended": int(r.get("contended", 0)),
          "wait_s": float(r.get("wait_s", 0.0)),
          "held_s": float(r.get("held_s", 0.0)),
          "held_max_s": float(r.get("held_max_s", 0.0))}
         for (host, name), r in per_lock.items()),
        key=lambda row: -row["held_s"])
    lock_report = {
        "locks": lock_rows[:20],
        "contended_total": sum(row["contended"] for row in lock_rows),
        "order_graph": (None if graph is None else {
            "edges": graph.get("edges"),
            "acyclic": graph.get("acyclic"),
            "cycle": graph.get("cycle"),
        }),
    }

    return {
        "records": len(events),
        "by_kind": by_kind,
        "runs": runs,
        "steps": step_report,
        "health": health_report,
        "ckpt": ckpt_report,
        "serve": serve_report,
        "prof": prof_report,
        "compiles": compile_report,
        "decode": decode_report,
        "sampler": sampler_report,
        "attention": attention_report,
        "mem": mem_report,
        "faults": faults,
        "data": data_report,
        "locks": lock_report,
        "torn_spans": [{"kind": r.get("kind"), "name": r.get("name"),
                        "host": r.get("host", 0), "seq": r.get("seq")}
                       for r in _torn_spans(events)][:20],
    }


def build_fleet_report(events: List[dict], clocks) -> dict:
    """The fleet view over ALIGNED, merged records (``align.merge_streams``
    output): everything :func:`build_report` aggregates — serve p50/p99
    and attainment per SLO class, ckpt/fault/quarantine rollups — now
    spans every host, plus the cross-host sections only an aligned
    timebase makes meaningful:

    * per-lane clock provenance (offset/drift/residual bound/method),
    * the global step timeline: for every step seen on >= 2 lanes, the
      fleet-time spread between the first and last host to log it,
    * straggler ranking: lanes ordered by their mean lag behind the
      fastest host at each common step,
    * active-alert rollup per lane.
    """
    rep = build_report(events)

    by_lane: Dict[int, List[dict]] = {}
    for r in events:
        by_lane.setdefault(int(r.get("host", 0)), []).append(r)

    lane_rows = []
    for c in clocks:
        lane = by_lane.get(c.lane, [])
        steps = [r for r in lane if r.get("kind") == "step"
                 and "ph" not in r and r.get("step") is not None]
        alerts = [r.get("name") for r in lane if r.get("kind") == "alert"]
        lane_rows.append(dict(
            c.summary(), records=len(lane),
            last_step=max((int(r["step"]) for r in steps), default=None),
            alerts=sorted(set(alerts)), alert_count=len(alerts)))

    # step timeline on the fleet timebase
    step_t: Dict[int, Dict[int, float]] = {}
    for r in events:
        if r.get("kind") != "step" or "ph" in r or r.get("step") is None \
                or r.get("t") is None:
            continue
        per = step_t.setdefault(int(r["step"]), {})
        per.setdefault(int(r.get("host", 0)), float(r["t"]))
    common = {s: per for s, per in step_t.items() if len(per) >= 2}
    spreads = sorted((max(per.values()) - min(per.values()))
                     for per in common.values())
    lags: Dict[int, List[float]] = {}
    for per in common.values():
        first = min(per.values())
        for lane, t in per.items():
            lags.setdefault(lane, []).append(t - first)
    stragglers = sorted(
        ({"lane": lane, "mean_lag_s": sum(v) / len(v),
          "max_lag_s": max(v), "steps": len(v)}
         for lane, v in lags.items()),
        key=lambda row: -row["mean_lag_s"])
    last_steps = [row["last_step"] for row in lane_rows
                  if row["last_step"] is not None]
    rep["fleet"] = {
        "lanes": lane_rows,
        "common_steps": len(common),
        "step_spread_p50_s": _pct(spreads, 50),
        "step_spread_max_s": spreads[-1] if spreads else None,
        "stragglers": stragglers,
        "steps_behind": (max(last_steps) - min(last_steps)
                         if len(last_steps) > 1 else None),
    }
    return rep


def _fmt(v, nd: int = 4) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.{nd}g}"
    return str(v)


def render_text(report: dict) -> str:
    """The human half: one screen answering "what happened to this run"."""
    lines: List[str] = []
    lines.append(f"== graftscope run report "
                 f"({report['records']} records) ==")
    for run_id, run in report["runs"].items():
        lines.append(f"run {run_id}: hosts {run['hosts']}, "
                     f"{run['records']} records, "
                     f"wall {_fmt(run['wall_s'])}s")
    lines.append("kinds: " + ", ".join(
        f"{k}={v}" for k, v in sorted(report["by_kind"].items())))

    s = report["steps"]
    lines.append("-- training --")
    if s.get("records"):
        lines.append(
            f"steps {s.get('first_step')}..{s.get('last_step')} "
            f"({s['records']} records): loss "
            f"{_fmt(s.get('loss_first'))} -> {_fmt(s.get('loss_last'))} "
            f"(min {_fmt(s.get('loss_min'))}), step_time p50 "
            f"{_fmt(s.get('step_time_p50'))}s, mfu {_fmt(s.get('mfu_last'))},"
            f" stall frac {_fmt(s.get('stall_frac_mean'))}")
        res = s.get("reservoir")
        if res:
            lines.append(
                f"reservoir (n={res.get('reservoir_n')}): step_time "
                f"p50 {_fmt(res.get('step_time_p50'))}s / p99 "
                f"{_fmt(res.get('step_time_p99'))}s, stall p50 "
                f"{_fmt(res.get('stall_p50'))}s / p99 "
                f"{_fmt(res.get('stall_p99'))}s")
    else:
        lines.append("no step records")

    h = report["health"]
    lines.append("-- health --")
    if h["verdicts"]:
        lines.append("verdicts: " + ", ".join(
            f"{k}={v}" for k, v in sorted(h["verdicts"].items())))
        for t in h["timeline"][:10]:
            lines.append(f"  step {t['step']} host {t['host']}: {t['name']} "
                         f"(loss {_fmt(t['loss'])})")
    else:
        lines.append("no health events")

    c = report["ckpt"]
    lines.append("-- checkpoints --")
    lines.append(
        f"publishes {c['publishes']} (steps {c['publish_steps']}), cadence "
        f"{_fmt(c['cadence_s'])}s, save dur p50 {_fmt(c['save_dur_p50'])}s "
        f"max {_fmt(c['save_dur_max'])}s, fallback skips "
        f"{c['fallback_skips']}, failed {c['failed_saves']}, torn "
        f"{c['torn_saves']}")

    sv = report["serve"]
    lines.append("-- serve --")
    ar = sv.get("arena")
    if ar:
        lines.append(
            f"arena layout: {ar['slots']} slots; caches of "
            f"{ar['folded_layers']} layers stored head-folded, "
            f"{ar['plain_layers']} plain, {ar['ring_layers']} rings, "
            f"{ar['recurrent_layers']} recurrent; an install writes "
            f"{ar['install_bytes_per_slot']} bytes, the compiled tick "
            f"relayouts {ar['tick_relayout_bytes']} (last of "
            f"{ar['arenas']} arenas)")
    if sv["submitted"] or sv["completed"]:
        lines.append(
            f"requests {sv['submitted']} submitted / {sv['completed']} "
            f"completed / {sv['failed']} failed, preemptions "
            f"{sv['preemptions']}, ticks {sv['ticks']}, tokens "
            f"{sv['decoded_tokens']}")
        pref = sv.get("prefix")
        if pref:
            lines.append(
                f"  prefix cache: {pref['hits']}/{pref['lookups']} hits "
                f"(rate {_fmt(pref['hit_rate'])}), entries "
                f"{pref['entries']}, prefill FLOPs saved "
                f"{_fmt(pref['prefill_flops_saved'])}")
        ph = sv.get("phases")
        if ph:
            lines.append(
                f"  steps recorded {ph['steps']}: {_fmt(ph['step_s'])}s, "
                f"median {_fmt(ph['step_median_s'])}s, self "
                f"{_fmt(ph['self_s'])}s ({_fmt(ph['self_share'])} of step "
                f"time)")
            for name, row in ph["phases"].items():
                lines.append(
                    f"  {name}: n={row['count']} total "
                    f"{_fmt(row['total_s'])}s median "
                    f"{_fmt(row['median_s'])}s share {_fmt(row['share'])}")
        for slo, row in sv["by_class"].items():
            lines.append(
                f"  {slo}: n={row['completed']} p50 "
                f"{_fmt(row['latency_p50'])}s p99 {_fmt(row['latency_p99'])}s"
                f" wait {_fmt(row['queue_wait_mean'])}s attainment "
                f"{_fmt(row['attainment'])}")
    elif not ar:
        lines.append("no serve events")

    prof = report.get("prof")
    if prof:
        lines.append("-- roofline (predicted vs measured) --")
        lines.append(
            f"ledger {prof.get('fingerprint')} "
            f"({'exact' if prof.get('exact') else 'plan-level'}, chip "
            f"{prof.get('chip')}): predicted mfu "
            f"{_fmt(prof.get('predicted_mfu'))} "
            f"({prof.get('bound')}-bound, step "
            f"{_fmt(prof.get('pred_step_time_s'))}s)")
        lines.append(
            f"measured: mfu {_fmt(prof.get('measured_mfu'))}, step_time p50 "
            f"{_fmt(prof.get('measured_step_time_p50'))}s -> attained "
            f"{_fmt(prof.get('attained_frac'))} of ceiling")

    comp = report.get("compiles")
    if comp:
        lines.append("-- compiles --")
        lines.append(", ".join(
            f"{phase} {row['count']}"
            + (f" ({_fmt(row['seconds'])}s)" if row["seconds"] else "")
            for phase, row in sorted(comp["phases"].items())))
        for row in comp["top"]:
            lines.append(f"  {row['fun']}: {_fmt(row['seconds'])}s")
        lines.append(f"traces after the first step record: "
                     f"{comp['traces_after_first_step']}")

    dec = report.get("decode")
    sam = report.get("sampler")
    if dec or sam:
        lines.append("-- decode --")
    if dec:
        lines.append(
            f"kv cache layout: {dec.get('kv_lane_dense_layers')} layers "
            f"lane-dense, {dec.get('kv_plain_layers')} plain "
            f"({dec.get('rows')} rows; last of {dec.get('traces')} "
            f"decode_codes traces)")
        if "reach" in dec:
            r = dec["reach"]
            lines.append(
                f"kv cache reach: {r.get('bounded_layers')} layers' dense "
                f"reads bounded by the position ({r.get('buckets')} "
                f"prefixes), {r.get('unbounded_layers')} as before; "
                f"{100 * (r.get('read_share') or 0):.1f}% of their slots "
                f"read a tick")
        if "kv_layers" in dec:
            lin = dec.get("linear", {})
            lines.append(
                f"decode state: {dec.get('kv_layers')} layers of keys and "
                f"values, "
                f"{(dec.get('ssm_layers') or 0) + lin.get('linear_layers', 0)}"
                f" recurrent; {dec.get('state_bytes_per_row')} bytes a row")
            if lin:
                lines.append(
                    f"linear attention: {lin.get('linear_layers')} of the "
                    f"recurrent layers, a float32 state of "
                    f"{lin.get('linear_state_shape')} a row"
                    + ("; state updated in one pass"
                       if lin.get("linear_one_pass_layers") else ""))
        if "latent" in dec:
            lat = dec["latent"]
            lines.append(
                f"latent cache: {lat.get('latent_layers')} layers, "
                f"{lat.get('latent_bytes_per_position')} bytes a position "
                f"({lat.get('latent_bytes_walked_per_position')} as stored)"
                + ({"one_pass": f"; read in one pass, {lat.get('block')} "
                                f"positions a block",
                    "two_pass": "; read in two passes"}.get(
                        lat.get("latent_read"), "")))
        if "moe" in dec:
            m = dec["moe"]
            lines.append(
                f"routed experts: {m.get('layers')} layers of "
                f"{m.get('experts')}, {m.get('experts_per_token')} a token, "
                f"at {dec.get('rows')} rows "
                f"({m.get('expert_bytes_per_layer')} bytes of banks a "
                f"layer); {m.get('window_layers')} window layers, "
                f"{m.get('kv_slots_per_row')} key slots a row")
        if "moe_share" in dec:
            sh = dec["moe_share"]
            lines.append(
                f"expert share: {sh.get('scoring')} scores, "
                f"{sh.get('experts_held')} held, "
                f"{sh.get('shared_experts')} shared")
    if sam:
        lines.append(
            f"sampler top-k: keeps {sam.get('k')} of {sam.get('vocab')} "
            f"logits, cut-off by {sam.get('method')} in "
            f"{sam.get('passes')} passes ({sam.get('rows')} rows; last of "
            f"{sam.get('traces')} sampler traces)")

    att = report.get("attention")
    if att:
        lines.append("-- attention --")
        lines.append(
            f"attention core: {att.get('flash_layers')} layers on the flash "
            f"kernel (tiles {', '.join(att.get('tiles') or []) or '-'}; "
            f"{100 * (att.get('blocks_computed_share') or 0):.1f}% of their "
            f"blocks computed; {att.get('heads_per_program')} heads a "
            f"program, {att.get('hbm_pad_rows')} rows of padding in HBM, "
            f"{att.get('backward_calls')} backward call(s) a layer), "
            f"{att.get('dense_layers')} dense "
            f"(n {att.get('n')}; last of {att.get('traces')} "
            f"{att.get('model')} traces; the kernel is lowered for a TPU "
            "only)")

    memr = report.get("mem")
    if memr:
        lines.append("-- memory (predicted vs measured) --")
        pred = memr.get("predicted")
        if pred:
            phases = pred.get("phases") or {}
            phase_txt = " ".join(
                f"{k}={int(v) / 2**20:.0f}MiB"
                for k, v in sorted(phases.items()))
            lines.append(
                f"ledger {pred.get('fingerprint')} "
                f"({'exact' if pred.get('exact') else 'plan-level'}, chip "
                f"{pred.get('chip')}): {phase_txt} -> peak "
                f"@{pred.get('peak_phase')}, headroom "
                f"{_fmt(pred.get('headroom_frac'))}"
                f"{'' if pred.get('fits') else ' (DOES NOT FIT)'}")
        for phase, w in memr.get("watermarks", {}).items():
            used = w.get("used_bytes")
            lines.append(
                f"  {phase}: used "
                f"{'-' if used is None else f'{used / 2**20:.0f}MiB'}"
                f" live {w.get('live_count', '-')} bufs"
                + (f", headroom {_fmt(w['headroom_frac'])}"
                   if w.get("headroom_frac") is not None else ""))
        peak = memr.get("peak_bytes")
        lk = memr.get("leak_checks", {})
        lines.append(
            f"measured peak {'-' if peak is None else f'{peak / 2**20:.0f}MiB'}"
            + (f", min headroom {_fmt(memr['headroom_frac_min'])}"
               if memr.get("headroom_frac_min") is not None else "")
            + (f"; leak checks {lk.get('total', 0)} "
               f"({lk.get('failed', 0)} FAILED)" if lk.get("total") else ""))

    if report["faults"]:
        lines.append("-- injected faults --")
        for f in report["faults"][:10]:
            lines.append(f"  {f['site']}:{f['action']} step {f['step']} "
                         f"(hit {f['hits']}, host {f['host']})")
    d = report["data"]
    if any(d.values()):
        lines.append(f"-- data -- sample quarantines "
                     f"{d['sample_quarantines']}, shard quarantines "
                     f"{d['shard_quarantines']}, loader stalls "
                     f"{d['loader_stalls']}")
    lk = report.get("locks") or {}
    if lk.get("locks"):
        lines.append("-- locks (graftrace witness) --")
        for row in lk["locks"][:8]:  # already sorted by held time, desc
            lines.append(
                f"  {row['name']} (host {row['host']}): "
                f"{row['acquires']} acquires, {row['contended']} contended "
                f"(wait {_fmt(row['wait_s'])}s), held {_fmt(row['held_s'])}s "
                f"total / {_fmt(row['held_max_s'])}s max")
        graph = lk.get("order_graph")
        if graph is not None:
            lines.append(
                f"  order graph: {graph.get('edges')} edge(s), "
                + ("acyclic" if graph.get("acyclic")
                   else f"CYCLE: {graph.get('cycle')}"))
    if report["torn_spans"]:
        lines.append("-- torn spans (death inside) --")
        for t in report["torn_spans"][:10]:
            lines.append(f"  {t['kind']}.{t['name']} host {t['host']} "
                         f"seq {t['seq']}")

    fleet = report.get("fleet")
    if fleet:
        lines.append("-- fleet (aligned timebase) --")
        for lane in fleet["lanes"]:
            bound = lane["residual_bound_s"]
            lines.append(
                f"  lane {lane['lane']} = {lane['run']} "
                f"(host {lane['host']}): offset {_fmt(lane['offset_s'])}s "
                f"drift {_fmt(lane['drift_s_per_s'])}/s "
                f"±{'unbounded' if bound is None else _fmt(bound) + 's'} "
                f"[{lane['method']}, {lane['anchors']} anchors], "
                f"last step {lane['last_step']}"
                + (f", ALERTS: {', '.join(lane['alerts'])}"
                   if lane["alerts"] else ""))
        lines.append(
            f"step timeline: {fleet['common_steps']} common steps, "
            f"spread p50 {_fmt(fleet['step_spread_p50_s'])}s / max "
            f"{_fmt(fleet['step_spread_max_s'])}s, steps behind "
            f"{_fmt(fleet['steps_behind'])}")
        for row in fleet["stragglers"][:5]:
            lines.append(
                f"  straggler lane {row['lane']}: mean lag "
                f"{_fmt(row['mean_lag_s'])}s (max {_fmt(row['max_lag_s'])}s "
                f"over {row['steps']} steps)")
    return "\n".join(lines) + "\n"
