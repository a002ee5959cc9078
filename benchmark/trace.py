"""The reduction from a profiler trace to what the per-layer metrics read.

`reduce` reads the `.xplane.pb` that `jax.profiler` wrote and keeps, for
each chip the cell uses, the intervals of the device's operations and of
the step program's runs, and the benchmark's own host spans (`bench.*`).
The metric readers in `benchmark/metrics/` take their numbers from the
`Trace` it returns, so every metric is reduced the same way.
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field


def xplane_path(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {found}")
    return found[0]


def summarize(path: str, top: int = 40) -> dict:
    """Planes, lines, event counts and the names that take most time: for
    looking at a trace by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            total = defaultdict(float)
            count = defaultdict(int)
            first = []
            for e in line.events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
                if len(first) < 3:
                    first.append({"name": e.name, "start_ns": e.start_ns,
                                  "dur_ns": e.duration_ns,
                                  "stats": [[k, str(v)[:200]]
                                            for k, v in e.stats]})
            names = sorted(total, key=total.get, reverse=True)[:top]
            lines.append({"line": line.name, "events": sum(count.values()),
                          "top": [[n, count[n], total[n]] for n in names],
                          "first": first})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


@dataclass
class Trace:
    """Intervals in ns on the trace's clock. `ops[d]`, `async_ops[d]`,
    `steps[d]`: (name, start, end) of chip d's operations (the line on
    which the device runs one op at a time), of its asynchronous ops
    (copies, async collectives: start to done) and of its step-program
    runs; `host`: the benchmark's own host spans. An op's name is
    `<instruction> <opcode>` (`op_name`)."""

    ops: dict = field(default_factory=dict)
    async_ops: dict = field(default_factory=dict)
    steps: dict = field(default_factory=dict)
    host: list = field(default_factory=list)
    chips: list = field(default_factory=list)

    # --- the steady window of each chip: its whole step programs, the
    # first traced one left out (the profiler starts inside it).
    def window(self, chip) -> tuple[int, int, int]:
        runs = self.steps[chip][1:]
        if not runs:
            raise RuntimeError(f"chip {chip}: fewer than two traced steps")
        return runs[0][1], runs[-1][2], len(runs)

    def busy_ns(self, chip) -> int:
        lo, hi, _ = self.window(chip)
        return union_ns([(s, e) for _, s, e in self.ops[chip]], lo, hi)

    @property
    def window_s(self) -> float:
        return sum(hi - lo for lo, hi, _ in map(self.window, self.chips)) \
            / len(self.chips) / 1e9

    @property
    def busy_s(self) -> float:
        return sum(map(self.busy_ns, self.chips)) / len(self.chips) / 1e9

    def _all_ops(self, chip):
        return self.ops[chip] + self.async_ops.get(chip, [])

    def op_ns(self, chip, match) -> tuple[int, int]:
        """(summed duration, steps) of chip's ops, asynchronous ones too,
        whose name `match`es, inside its window."""
        lo, hi, n = self.window(chip)
        return sum(min(e, hi) - max(s, lo)
                   for name, s, e in self._all_ops(chip)
                   if match(name) and e > lo and s < hi), n

    def exposed_ns(self, chip, match) -> tuple[int, int]:
        """(time in which a `match`ing op runs and no other op runs on the
        one-at-a-time line, steps), inside chip's window."""
        lo, hi, n = self.window(chip)
        mine = [(s, e) for name, s, e in self._all_ops(chip) if match(name)]
        other = [(s, e) for name, s, e in self.ops[chip] if not match(name)]
        return (union_ns(mine, lo, hi)
                - overlap_ns(mine, other, lo, hi)), n

    def breakdown(self, top: int = 10) -> dict:
        chip = self.chips[0]
        lo, hi, _ = self.window(chip)
        total = defaultdict(int)
        for name, s, e in self.ops[chip]:
            if e > lo and s < hi:
                total[name] += min(e, hi) - max(s, lo)
        ops = sorted(total.items(), key=lambda kv: kv[1], reverse=True)
        gaps = []
        for s, e in idle_gaps([(s, e) for _, s, e in self.ops[chip]],
                              lo, hi):
            gaps.append((self.host_label(s, e), (e - s) / 1e9))
        gaps.sort(key=lambda g: g[1], reverse=True)
        return {"device_ops": [[n, v / 1e9] for n, v in ops[:top]],
                "idle_gaps": [[n, v] for n, v in gaps[:top]]}

    def host_label(self, lo, hi) -> str:
        """The host span that covers most of [lo, hi)."""
        best, cover = "no bench span", 0
        for name, s, e in self.host:
            c = min(e, hi) - max(s, lo)
            if c > cover:
                best, cover = name, c
        return best


def union_ns(intervals, lo, hi) -> int:
    total, end = 0, lo
    for s, e in sorted(intervals):
        s, e = max(s, end), min(e, hi)
        if e > s:
            total += e - s
            end = e
    return total


def overlap_ns(a, b, lo, hi) -> int:
    """Length of (union of a) intersected with (union of b) in [lo, hi)."""
    ua, ub = merged(a, lo, hi), merged(b, lo, hi)
    total, j = 0, 0
    for s, e in ua:
        while j < len(ub) and ub[j][1] <= s:
            j += 1
        k = j
        while k < len(ub) and ub[k][0] < e:
            total += min(e, ub[k][1]) - max(s, ub[k][0])
            k += 1
    return total


def merged(intervals, lo, hi) -> list:
    out = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def idle_gaps(intervals, lo, hi) -> list:
    gaps, end = [], lo
    for s, e in merged(intervals, lo, hi):
        if s > end:
            gaps.append((end, s))
        end = e
    if hi > end:
        gaps.append((end, hi))
    return gaps


OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
STEP_LINE = "XLA Modules"
STEP_PROGRAM = "jit_step("
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9_\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_name(hlo: str) -> str:
    """`<instruction> <opcode> <shape>` from an op event's name, which on a
    TPU is the HLO instruction's text: `%fusion.16 =
    f32[50304,768]{1,0:T(8,128)} fusion(...)` gives `fusion.16 fusion
    f32[50304,768]`. Matching on the whole text would also match the
    operands it names."""
    lhs, _, rhs = hlo.partition(" = ")
    m = _OPCODE.search(rhs)
    if not m:
        return f"{lhs.lstrip('%')} ?"
    shape = _LAYOUT.sub("", rhs[:m.start()]).replace(" ", "")[:80]
    return f"{lhs.lstrip('%')} {m.group(1)} {shape}"


def reduce(path: str, chips) -> Trace:
    from jax.profiler import ProfileData

    t = Trace(chips=list(chips))
    for plane in ProfileData.from_file(path).planes:
        name = plane.name
        if name.startswith("/device:TPU:"):
            chip = int(name.split(":")[2].split(" ")[0])
            if chip not in t.chips:
                continue
            for line in plane.lines:
                if line.name not in (OP_LINE, ASYNC_LINE, STEP_LINE):
                    continue
                events = [(e.name, int(e.start_ns),
                           int(e.start_ns + e.duration_ns))
                          for e in line.events]
                if line.name == STEP_LINE:
                    t.steps[chip] = sorted(
                        ev for ev in events if ev[0].startswith(STEP_PROGRAM))
                else:
                    named = [(op_name(n), s, e) for n, s, e in events]
                    (t.ops if line.name == OP_LINE else t.async_ops)[chip] = \
                        named
        elif name.startswith("/host:"):
            for line in plane.lines:
                t.host += [(e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns))
                           for e in line.events if e.name.startswith("bench.")]
    missing = [c for c in t.chips if c not in t.ops or not t.steps.get(c)]
    if missing:
        raise RuntimeError(f"no device ops or step runs for chips {missing} "
                           f"in {path}")
    return t


def is_flash(name: str) -> bool:
    """The flash kernels: custom calls named after `flash_attention` (the
    forward's instruction is `jvp_jit_flash_attention__.N`, the fused
    backward's `flash_attention_bwd_fused.N`)."""
    instr, opcode = name.split(" ")[:2]
    return opcode == "custom-call" and "flash_attention" in instr


def is_all_reduce(name: str) -> bool:
    """All-reduce ops by their opcode: `all-reduce`, or an async pair's
    `all-reduce-start` / `all-reduce-done`."""
    return name.split(" ")[1].startswith("all-reduce")


def to_json(t: Trace) -> str:
    return json.dumps({"chips": t.chips, "ops": t.ops,
                       "async_ops": t.async_ops, "steps": t.steps,
                       "host": t.host})


def from_json(text: str) -> Trace:
    d = json.loads(text)

    def per_chip(m):
        return {int(k): [tuple(x) for x in v] for k, v in m.items()}

    return Trace(ops=per_chip(d["ops"]), async_ops=per_chip(d["async_ops"]),
                 steps=per_chip(d["steps"]),
                 host=[tuple(x) for x in d["host"]], chips=d["chips"])

