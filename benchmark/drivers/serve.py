"""A caption list drained through one ``GenerationServer``: a staggered closed
loop at saturation.  ``clients`` clients each keep one request outstanding
(one caption = one request = one picture); the loop is the synchronous one
``serve/scheduler.py`` documents: one thread calls ``step()``, then takes
every newly completed handle and dispatches the decode of its codes to a
picture (``cli.make_decode_fn``, one picture a call); after the NEXT step it
fetches that picture to the host and submits that client's next caption.  A
request is finished when its picture is on the host.  (The fetch waits one
step because a fetch straight after the decode leaves the device with nothing
queued while the picture crosses to the host: a gap the client opens, not the
server, and the one part of this loop whose length differed from process to
process on the chip: PERF.md, Findings PR 36.)

**Fill, before the window** (counted in ``setup_s``): client j < ``num_slots``
submits after scheduler step ``stagger * j``, so the slots sit ``stagger``
ticks apart over a request's life and stay so whatever a tick costs: one
retirement and one admission every ``stagger`` ticks.  The other clients
submit when the last slot is taken, so the queue holds ``clients -
num_slots`` from then on.  **Window**: from the first completion after
``warm_completions`` have been seen to the first completion at or after
``--seconds``; ``gen_tokens_per_s`` is the completions in it times the codes
of a picture over that span, completion to completion.  **Traced**: after the
window a telemetry stream is opened under ``benchmark/out/telemetry/<cell>/``
and ``traced_steps`` more steps run under ``jax.profiler``, each inside
``bench:serve_step``, the client's decode inside ``bench:client_decode``; the
scheduler's own ``graft:serve.*`` spans land on the same clock
(``layer_metrics/_serve.py``).

Traffic parameters: ``num_slots``, ``clients``, ``stagger``,
``filter_thres``, ``temperature``, ``text``, ``warm_completions``,
``traced_steps``, ``check_requests``.
"""
from __future__ import annotations

import collections
import gc
import shutil
import time

import jax
import numpy as np

from benchmark import checks, harness

#: captions tokenised ahead; the clients cycle through them
PROMPTS = 1024
#: what the readers take of the traced stretch's `serve.tick` and
#: `serve.admit` records (``layer_metrics/_serve.py``)
TRACED_RECORDS = {"tick": ("clock", "ticks", "active_sum"),
                  "admit": ("rid", "slot", "queue_wait_s")}


class Loop:
    """The server, its clients and the picture decode: :meth:`step` is one
    scheduler iteration followed by the clients' turn."""

    def __init__(self, cell, dalle_cfg, vae_cfg, seed, tracer):
        from dalle_pytorch_tpu.cli import make_decode_fn
        from dalle_pytorch_tpu.serve import GenerationServer

        tr = cell.traffic
        self.tracer = tracer
        self.dalle, vae, init_dalle, init_vae = harness.init_fns(dalle_cfg,
                                                                 vae_cfg)
        k_model, k_vae, _ = jax.random.split(jax.random.PRNGKey(seed), 3)
        self.params = jax.jit(init_dalle)(k_model)
        self.vae_decode = make_decode_fn(vae, jax.jit(init_vae)(k_vae))
        self.prompts = harness.make_prompts(cell, dalle_cfg, PROMPTS, seed)
        self.temperature = float(tr["temperature"])
        self.server = GenerationServer(
            self.dalle, {"params": self.params},
            num_slots=int(tr["num_slots"]),
            filter_thres=float(tr["filter_thres"]), seed=seed % 2 ** 32)
        self.picture_shape = (vae_cfg.image_size, vae_cfg.image_size, 3)
        self.sent = self.seen = 0
        self.decoding = []                # (handle, picture on the device)
        self.done_at = []                 # perf_counter of each completion
        self.pictures_ok = True
        self.last = collections.deque(maxlen=int(tr["check_requests"]))
        # the decode program, compiled before the first completion needs it
        jax.block_until_ready(self.decode(
            np.zeros((dalle_cfg.image_seq_len,), np.int32)))

    def decode(self, codes):
        return self.vae_decode(codes[None])

    def submit(self) -> None:
        self.server.submit(self.prompts[self.sent % len(self.prompts)],
                           temperature=self.temperature)
        self.sent += 1

    def step(self) -> None:
        with self.tracer.span("bench:serve_step"):
            self.server.step()
        for handle, decoding in self.decoding:
            # dispatched after the last step, so behind a tick that step
            # queued and ahead of the one this step did
            with self.tracer.span("bench:client_decode"):
                picture = np.asarray(jax.device_get(decoding))[0]
            self.done_at.append(time.perf_counter())
            self.pictures_ok &= (picture.shape == self.picture_shape
                                 and bool(np.isfinite(picture).all()))
            self.last.append(handle)
            self.submit()                 # that client's next caption
        self.decoding = [(handle, self.decode(handle.result()))
                         for handle in self.server.completed[self.seen:]]
        self.seen = len(self.server.completed)

    def fill(self, clients: int, stagger: int) -> None:
        slots = self.server.num_slots
        life = self.server.arena.geometry.image_seq_len - 1   # ticks
        if clients < slots or (slots - 1) * stagger + 1 >= life:
            raise harness.BenchError(
                f"{slots} slots {stagger} ticks apart do not fit the "
                f"{life} ticks of a request, or {clients} clients cannot "
                "keep them taken")
        for _ in range(slots - 1):
            self.submit()
            for _ in range(stagger):
                self.step()
        self.submit()
        self.step()                       # admits the last slot's client
        for _ in range(clients - slots):
            self.submit()

    def drain(self) -> None:
        """Wait for every dispatched tick: the profiler then starts and
        stops on an empty device queue."""
        jax.block_until_ready(self.server.arena.state["out"])


def slot_ticks(stats: dict, slots: int) -> int:
    return round(stats["occupancy"] * stats["ticks"] * slots)


def judge(dalle, params, handles, filter_thres, *, occupancy, trace_counts,
          failed, pictures_ok) -> dict:
    """What decides ``correct``: the last finished requests' codes against
    the plain reference (``checks.compare``: logits within the limit, every
    sampled code inside the reference's top-k and in range), every picture
    whole, nothing retraced, nothing failed, and no hole in the arena over
    the window (a saturated server with one is a wrong run)."""
    verdict = checks.compare(
        dalle, params, np.concatenate([h.text for h in handles]),
        np.stack([h.result() for h in handles]), filter_thres)
    verdict.update(
        occupancy=occupancy, trace_counts=trace_counts, failed=failed,
        pictures_ok=bool(pictures_ok),
        ok=bool(verdict["ok"] and pictures_ok and failed == 0
                and occupancy == 1.0
                and all(v == 1 for v in trace_counts.values())))
    return verdict


def run(cell, devices, dalle_cfg, vae_cfg, seed, seconds, tracer, mark_ready):
    from dalle_pytorch_tpu.obs import telemetry
    from dalle_pytorch_tpu.serve import SlotArena

    if not hasattr(SlotArena, "programs"):
        # a program from before the arena's programs were named: its trace
        # holds no jit_serve_* to read, so there is no cell to run
        raise harness.BenchError("this program's SlotArena names no programs")
    tr = cell.traffic
    loop = Loop(cell, dalle_cfg, vae_cfg, seed, tracer)
    server, slots = loop.server, int(tr["num_slots"])
    image_len = dalle_cfg.image_seq_len

    loop.fill(int(tr["clients"]), int(tr["stagger"]))
    while len(loop.done_at) < int(tr["warm_completions"]):
        loop.step()
    # what a server does once it is warm: a full collection walks everything
    # set-up allocated (tens of ms), and the collector only ever runs while
    # the loop dispatches, which is where the queue of dispatched ticks is
    # shallowest; frozen, a collection walks what the loop allocated since
    gc.collect()
    gc.freeze()
    while len(loop.done_at) <= int(tr["warm_completions"]):
        loop.step()
    t0, n0, stats0 = loop.done_at[-1], len(loop.done_at), server.stats()
    mark_ready(at=t0)
    while loop.done_at[-1] - t0 < seconds:
        loop.step()
    t1, n_done, stats1 = loop.done_at[-1], len(loop.done_at) - n0, \
        server.stats()
    occupancy = ((slot_ticks(stats1, slots) - slot_ticks(stats0, slots))
                 / ((stats1["ticks"] - stats0["ticks"]) * slots))
    tokens_per_s = n_done * image_len / (t1 - t0)

    traced = {}
    if tracer.on:
        stream = harness.OUT / "telemetry" / cell.name
        shutil.rmtree(stream, ignore_errors=True)
        loop.drain()
        telemetry.init(stream, run_id=cell.name, beacon_every=0)
        tracer.start()
        for _ in range(int(tr["traced_steps"])):
            loop.step()
        loop.drain()
        tracer.stop()
        telemetry.shutdown()
        events = [r for r in telemetry.read_events(stream)
                  if r.get("kind") == "serve" and "ph" not in r]
        traced = {f"traced_{name}s": [{k: r[k] for k in keep}
                                      for r in events if r["name"] == name]
                  for name, keep in TRACED_RECORDS.items()}

    gc.unfreeze()
    memory_peak = harness.memory_peak_bytes(devices)
    trace_counts = server.trace_counts()
    trace_counts["vae_decode"] = int(loop.vae_decode._cache_size())
    verdict = judge(loop.dalle, loop.params, list(loop.last),
                    float(tr["filter_thres"]), occupancy=occupancy,
                    trace_counts=trace_counts, failed=len(server.failed),
                    pictures_ok=loop.pictures_ok)
    return harness.Outcome(
        correct=verdict["ok"], attempted=n_done, failed=len(server.failed),
        end_to_end={"gen_tokens_per_s": tokens_per_s},
        host={"tokens_per_s": tokens_per_s, "requests": n_done,
              "rows": slots, "window_s": t1 - t0,
              "window_steps": stats1["ticks"] - stats0["ticks"],
              "stats": stats1, "check": verdict, **traced},
        programs=server.arena.programs() if tracer.on else {},
        main_program="jit_serve_tick", memory_peak_bytes=memory_peak,
        notes=[f"{n_done} requests of {image_len} codes over {slots} slots; "
               f"check {verdict}"])
