"""Shared by the readers of the state-space scopes (``ops/ssm.py``:
``ssm-proj``, ``ssm-conv``, ``ssm-scan``).  A program without such scopes (a
checkout from before PR 27, a configuration without Mamba layers) reads
None and the metric is left out of the line.

XLA:TPU feeds a fusion from fast memory: it copies and slices the operands
there ahead of time (``copy-start``/``-done``, ``slice-start``/``-done``),
and the core's waits for those transfers are events of their own that carry
no ``op_name``, so ``trace_reduce`` counts them under no scope.  At
``jamba2-3b`` the recurrent state reaches its fusion that way, a quarter of
the batch at a time, after a whole copy of the carried state; the fusion
alone then reads its 84 MB in 91 us, faster than the chip's memory (my chip
run, PR 27).  ``update_seconds`` therefore adds to the scoped time every such
wait of the decode program except those that the compiled program's own text
ties to another cost centre (the wait's result reaches, or is made from, an
instruction under that scope): the waits of the recurrent update, and those
no scope claims (the loop's copy of the carried state is among them).  What
no scope claims may be another's, so the time errs long and a roofline share
over it errs low.
"""
from __future__ import annotations

import functools
import re
import sys

from benchmark import harness, trace_reduce

SCAN_SCOPES = ("ssm-conv", "ssm-scan")
UMBRELLA = ("decode-step", "serve-tick")
PROGRAM = "jit_bench_decode"
OPERAND_RE = re.compile(r"%([\w.\-]+)")


def scan_seconds(run):
    """Device self seconds under ``ssm-conv`` + ``ssm-scan`` in the traced
    stretch, or None where nothing ran under them."""
    if run.trace is None:
        return None
    seconds = sum(run.trace.scope_s.get(s, 0.0) for s in SCAN_SCOPES)
    return seconds or None


def ticks_traced(run):
    """Decode ticks the traced stretch ran: calls of the decode program
    times the token steps a call makes."""
    program = (run.trace.program(PROGRAM)
               if run.trace is not None else None)
    if program is None:
        return None
    return program["calls"] * run.outcome.host["decode_steps_traced"]


def consumer_scope(hlo_text: str, scopes: dict):
    """``name -> scope``: an instruction's own scope, else the scope of the
    first instruction its result reaches through instructions that have none
    (at most eight deep), else that of the first instruction it is made
    from, else None.  The umbrella scopes count as none: what matters is the
    cost centre inside them."""
    users, operands = {}, {}
    for line in hlo_text.splitlines():
        m = trace_reduce.INSTR_RE.match(line)
        if m:
            found = list(dict.fromkeys(OPERAND_RE.findall(line[m.end():])))
            operands[m.group(1)] = found
            for operand in found:
                users.setdefault(operand, []).append(m.group(1))

    def walk(edges):
        @functools.lru_cache(maxsize=None)
        def reach(name, depth=0):
            if scopes.get(name, UMBRELLA[0]) not in UMBRELLA:
                return scopes[name]
            if depth < 8:
                for nxt in edges.get(name, ()):
                    found = reach(nxt, depth + 1)
                    if found:
                        return found
            return None
        return reach

    forward, backward = walk(users), walk(operands)
    return lambda name: forward(name) or backward(name)


@functools.lru_cache(maxsize=2)
def _wait_seconds(xplane, program):
    """``{scope or None: seconds}`` of the decode program's unscoped transfer
    waits (``*-done``), by the scope they feed; the chips' mean."""
    text = program.as_text()
    scopes = trace_reduce.scopes_of(text)
    reach = consumer_scope(text, scopes)
    devices = trace_reduce.extract(xplane)["devices"]
    out = {}
    for dev in devices:
        for name, prog, own in trace_reduce.self_times(dev["ops"]):
            if (prog == PROGRAM and "-done" in name
                    and scopes.get(name, UMBRELLA[0]) in UMBRELLA):
                scope = reach(name)
                out[scope] = out.get(scope, 0.0) + own / 1e9
    out = {k: v / max(len(devices), 1) for k, v in out.items()}
    print(f"[bench] transfer waits of {PROGRAM} by the scope they feed "
          f"(s): { {str(k): round(v, 4) for k, v in out.items()} }",
          file=sys.stderr, flush=True)
    return out


def update_seconds(run):
    """Device seconds the recurrent update took in the traced stretch: its
    scoped self time plus the core's waits for transfers that feed it."""
    seconds = scan_seconds(run)
    program = run.outcome.programs.get(PROGRAM)
    xplane = (harness.Tracer(True, run.cell.name).xplane()
              if run.cell is not None else None)
    if seconds is None or program is None or xplane is None:
        return seconds
    waits = _wait_seconds(xplane, program)
    return seconds + sum(waits.get(s, 0.0) for s in SCAN_SCOPES + (None,))
