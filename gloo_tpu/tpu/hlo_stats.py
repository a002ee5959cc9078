"""What a compiled program sends over ICI, read from its optimized HLO.

`collective_stats(compiled)` answers an operator's questions about a
jitted step without running it: how many collectives each program run
issues, how many bytes each device hands them, and in which dtype ("does
my step send the embedding gradient in f32?"). It reads
`compiled.as_text()`, the per-device program after SPMD partitioning and
the collective combiner, so a tuple all-reduce that the combiner merged
from many gradients counts as one call carrying all of their bytes.

    compiled = step.lower(params, opt_state, batch).compile()
    stats = gloo_tpu.tpu.collective_stats(compiled)
    stats.calls["all-reduce"], stats.bytes["all-reduce"] / 1e6  # calls, MB
    stats.dtypes["all-reduce"]           # {"f32": bytes, "bf16": bytes}
    stats.op_names["psum_invariant.616"]  # the instruction's op_name
    stats.pallas_calls["flash_attention"]  # the flash forward's launches

Counts are static: a collective inside a loop body counts once, and a
`ragged-all-to-all` counts its whole operand and output buffers (sized
for the worst case), not the rows a run sends.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

# Synchronous collectives and the start half of their async pairs; the
# `-done` half carries no bytes of its own.
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "ragged-all-to-all", "collective-permute")
OPCODES = COLLECTIVES + tuple(op + "-start" for op in COLLECTIVES)

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9_\-]*)\(")
_ARRAY = re.compile(r"\b([a-z][a-z0-9]*)\[([\d,]*)\]")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_JIT = re.compile(r"jit\(([\w.\-]+)\)")


@dataclass(frozen=True)
class CollectiveStats:
    """Per device and per program run: `calls[opcode]`, operand
    `bytes[opcode]` and their split by element type `dtypes[opcode]`, for
    each collective opcode the program issues; `op_names[instruction]`,
    the `op_name` metadata (JAX's name stack) of every instruction that
    has one; `pallas_calls[name]`, the Pallas kernel launches by the
    innermost `jit(...)` of their `op_name`, the jitted function that
    launched them: `flash_attention` for the flash forward (its
    rematerialized copies too), `flash_attention_bwd_fused` for its
    backward."""

    calls: dict = field(default_factory=dict)
    bytes: dict = field(default_factory=dict)
    dtypes: dict = field(default_factory=dict)
    op_names: dict = field(default_factory=dict)
    pallas_calls: dict = field(default_factory=dict)


def array_bytes(type_text: str) -> dict:
    """{element type: bytes} of the arrays in an HLO type, e.g.
    `(bf16[768,2304]{1,0}, f32[])` gives {"bf16": 3538944, "f32": 4}."""
    out = {}
    for dtype, dims in _ARRAY.findall(type_text):
        digits = re.search(r"\d+", dtype)
        bits = 8 if dtype == "pred" else int(digits[0]) if digits else 0
        elems = 1
        for d in filter(None, dims.split(",")):
            elems *= int(d)
        out[dtype] = out.get(dtype, 0) + elems * bits // 8
    return out


def _operands(rhs: str, start: int) -> list:
    """Names of the operands in the parenthesis that opens at `start`."""
    depth = 0
    for i in range(start, len(rhs)):
        depth += {"(": 1, ")": -1}.get(rhs[i], 0)
        if depth == 0:
            return re.findall(r"%([\w.\-]+)", rhs[start:i])
    return re.findall(r"%([\w.\-]+)", rhs[start:])


def collective_stats(compiled) -> CollectiveStats:
    """Collective calls and operand bytes of one run of `compiled` (a
    `jax.stages.Compiled`, or the text of its `as_text()`) on one device,
    every instruction's `op_name`, and its Pallas kernel launches."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    types, found = {}, []
    stats = CollectiveStats()
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        op = _OPCODE.search(rhs)
        if not op:
            continue
        types[name] = rhs[:op.start()]
        named = _OP_NAME.search(rhs)
        if named:
            stats.op_names[name] = named.group(1)
        if op.group(1) == "custom-call" and named and \
                named.group(1).endswith("/pallas_call"):
            kernel = (_JIT.findall(named.group(1)) or ["pallas_call"])[-1]
            stats.pallas_calls[kernel] = stats.pallas_calls.get(kernel, 0) + 1
        if op.group(1) in OPCODES:
            found.append((op.group(1), _operands(rhs, op.end() - 1)))
    for opcode, operands in found:
        stats.calls[opcode] = stats.calls.get(opcode, 0) + 1
        split = stats.dtypes.setdefault(opcode, {})
        for operand in operands:
            for dtype, n in array_bytes(types.get(operand, "")).items():
                split[dtype] = split.get(dtype, 0) + n
        stats.bytes[opcode] = sum(split.values())
    return stats
