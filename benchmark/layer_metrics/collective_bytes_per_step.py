"""Bytes the compiled step's collectives move per device and step, counted
from the HLO: the result shapes of every all-reduce, all-gather,
reduce-scatter, all-to-all and collective-permute.  Repeats exactly."""
import re

SIZES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
         "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2, "u16": 2}
OP = re.compile(r"= (.*?) (all-reduce|all-gather|reduce-scatter|all-to-all|"
                r"collective-permute)(-start)?\(")
SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")


def read(run):
    program = run.outcome.programs.get(run.outcome.main_program)
    if program is None or len(run.devices) < 2:
        return None
    total = 0
    for line in program.as_text().splitlines():
        m = OP.search(line)
        if not m:
            continue
        for dtype, dims in SHAPE.findall(m.group(1)):
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            total += n * SIZES.get(dtype, 0)
    return float(total) or None
