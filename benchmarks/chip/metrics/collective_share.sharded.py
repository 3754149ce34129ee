"""Share of the traced window each chip spent in collectives: the self
time of every all-reduce, all-gather, reduce-scatter, collective-permute
and all-to-all op (mean over the chips, as `xplane` keeps op times) over
the window.  Time a chip spends waiting inside a collective counts.

An op is a collective by its HLO opcode, read from the instruction text
the trace carries, or by its name: JAX names an instruction after the
primitive that made it, so the all-reduce of a `psum` is an op `psum`."""

import re

PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
            "collective-permute", "all-to-all")

# "%psum.3 = f32[] all-reduce(f32[] %x), ..." -> "all-reduce": the first
# lower-case word that opens a parenthesis after the result's shape.
_OPCODE = re.compile(r" = .*?\b([a-z][a-z\-]*)\(")


def opcode(text: str) -> str:
    m = _OPCODE.search(text)
    return m.group(1) if m else ""


def is_collective(name: str, op) -> bool:
    return name.startswith(PREFIXES) or any(
        opcode(text).startswith(PREFIXES) for text in op.texts)


def read(run):
    seconds = sum(op.seconds for name, op in run.trace.ops.items()
                  if is_collective(name, op))
    return 100.0 * seconds / run.trace.window_s
