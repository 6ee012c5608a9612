"""Read the profiler's trace of the window and attribute the device's time.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  On a TPU each chip is a plane ``/device:TPU:<n>`` whose
``XLA Ops`` line holds one event per executed HLO instruction, named by
the instruction.  The harness's own host phases are ``TraceAnnotation``
spans named ``bench/<phase>`` on a host plane, on the same clock.

An instruction name alone does not say what work it does: three Pallas
bodies share a name, and XLA names its fusions by number.  The compiled
window program's text (``Compiled.as_text()``) does: each instruction's
``metadata`` names a stack frame, and the module's ``StackFrames`` table
walks that frame out through the program's own files and functions.  So
a Pallas call and the XLA fusions that would replace it are attributed to
the same function of the program.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

#: Prefix of the harness's host spans.
SPAN = "bench/"
#: The host span that encloses the traced window.
WINDOW = SPAN + "window"


# ------------------------------------------------------------ HLO metadata
_TABLE = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")
#: Instructions whose events enclose the ops they run, not work of their own.
CONTROL = frozenset({"while", "conditional", "call", "async-start",
                     "async-done", "async-update"})


class HloIndex:
    """Instruction name -> the program's call stack, from module text."""

    def __init__(self, text: str):
        files, funcs, locs, frames = {}, {}, {}, {}
        self.op_name: dict[str, str] = {}
        self.opcode: dict[str, str] = {}
        self._frame_of: dict[str, int] = {}
        table = None
        for line in text.splitlines():
            s = line.strip()
            if _TABLE.match(s):
                table = s
                continue
            if table and s and s[0].isdigit():
                key, _, rest = s.partition(" ")
                if table == "FileNames":
                    files[int(key)] = rest.strip('"')
                elif table == "FunctionNames":
                    funcs[int(key)] = rest.strip('"')
                else:
                    kv = dict(re.findall(r"(\w+)=(\d+)", rest))
                    if table == "FileLocations":
                        locs[int(key)] = (int(kv["file_name_id"]),
                                          int(kv["function_name_id"]))
                    else:
                        frames[int(key)] = (int(kv["file_location_id"]),
                                            int(kv.get("parent_frame_id", 0)))
                continue
            table = None
            m = _INSTR.match(line)
            if m:
                name = m.group(1)
                code = _OPCODE.search(line, m.end() - 1)
                self.opcode[name] = code.group(1) if code else ""
                op = re.search(r'op_name="([^"]*)"', line)
                sf = re.search(r"stack_frame_id=(\d+)", line)
                self.op_name[name] = op.group(1) if op else ""
                if sf:
                    self._frame_of[name] = int(sf.group(1))
        self._files, self._funcs = files, funcs
        self._locs, self._frames = locs, frames

    def stack(self, instr: str) -> list[tuple[str, str]]:
        """``(file, function)`` frames of ``instr``, innermost first.
        A frame's ``parent_frame_id`` is one above its parent's id (0:
        none)."""
        out, fid, seen = [], self._frame_of.get(instr), set()
        while fid and fid in self._frames and fid not in seen:
            seen.add(fid)
            loc, parent = self._frames[fid]
            f, fn = self._locs.get(loc, (0, 0))
            out.append((self._files.get(f, "?"), self._funcs.get(fn, "?")))
            fid = parent - 1
        return out


def _base(name: str) -> str:
    """The HLO instruction an event ran: on a TPU the event is named by
    the instruction's text, ``%fusion.12 = f32[...] fusion(...)``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


# ------------------------------------------------------------------- trace
@dataclass
class Trace:
    """Device events and host spans of one traced window, in ns."""
    chips: int
    window: tuple[int, int]
    ops: list = field(default_factory=list)     # (chip, name, start, end)
    spans: list = field(default_factory=list)   # (name, start, end)
    hlo: HloIndex | None = None

    @classmethod
    def read(cls, trace_dir: str, hlo_text: str | None = None) -> "Trace":
        from jax.profiler import ProfileData

        paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        data = ProfileData.from_file(paths[-1])
        ops, spans, chips = [], [], 0
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                chips += 1
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops += [(plane.name, _base(e.name), int(e.start_ns),
                                 int(e.end_ns)) for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans += [(e.name, int(e.start_ns), int(e.end_ns))
                              for e in line.events
                              if e.name.startswith(SPAN)]
        wins = [(s, e) for n, s, e in spans if n == WINDOW]
        if not wins:
            raise ValueError(f"the trace holds no {WINDOW!r} span")
        return cls(chips=max(chips, 1), window=wins[0], ops=ops, spans=spans,
                   hlo=HloIndex(hlo_text) if hlo_text else None)

    # ---------------------------------------------------------- intervals
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clipped(self, pred=None, leaves=False):
        """Ops inside the window, cut to it; ``leaves`` drops the control
        flow instructions whose events enclose other ops."""
        lo, hi = self.window
        for chip, name, s, e in self.ops:
            if leaves and self.hlo and self.hlo.opcode.get(name) in CONTROL:
                continue
            if pred is not None and not pred(name):
                continue
            s, e = max(s, lo), min(e, hi)
            if e > s:
                yield chip, name, s, e

    def busy_intervals(self, chip: str) -> list[tuple[int, int]]:
        """Union of the chip's op intervals inside the window."""
        iv = sorted((s, e) for c, _, s, e in self._clipped() if c == chip)
        out: list[list[int]] = []
        for s, e in iv:
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return [tuple(x) for x in out]

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        chips = sorted({c for c, *_ in self.ops}) or ["?"]
        total = sum(e - s for c in chips for s, e in self.busy_intervals(c))
        return total * 1e-9 / len(chips)

    # -------------------------------------------------------- attribution
    def frames(self, name: str) -> list[tuple[str, str]]:
        return self.hlo.stack(name) if self.hlo else []

    def where(self, name: str) -> str:
        """``file:function`` of the op's innermost frame in the program
        (``src/repro``), else its instruction name."""
        for f, fn in self.frames(name):
            if "/repro/" in f:
                return f"{f.split('/repro/', 1)[1]}:{fn}"
        return name

    def matches(self, name: str, frames=(), op_names=()) -> bool:
        """Whether op ``name`` does the work that ``frames`` (pairs of a
        file suffix and a function, ``"*"`` for any, a function matching
        any part of a qualified name) and ``op_names`` (substrings of the
        instruction's ``op_name``) describe: any frame of its call stack
        matches, or its ``op_name`` holds one of the substrings."""
        op = self.hlo.op_name.get(name, "") if self.hlo else ""
        if any(sub in op for sub in op_names):
            return True
        return any(f.endswith(suffix)
                   and (rule == "*" or rule in fn.split("."))
                   for f, fn in self.frames(name) for suffix, rule in frames)

    def attributed_s(self, frames=(), op_names=()) -> float:
        """Device seconds of the ops that ``matches`` picks, summed over
        chips and divided by their number."""
        cache: dict[str, bool] = {}

        def hit(name):
            if name not in cache:
                cache[name] = self.matches(name, frames, op_names)
            return cache[name]

        chips = len({c for c, *_ in self.ops}) or 1
        return sum(e - s for _, _, s, e in self._clipped(hit, leaves=True)) \
            * 1e-9 / chips

    # ---------------------------------------------------------- breakdown
    def top_ops(self, n: int = 10) -> list[list]:
        """Device seconds by attributed ``file:function``, largest first."""
        tot: dict[str, int] = {}
        for _, name, s, e in self._clipped(leaves=True):
            k = self.where(name)
            tot[k] = tot.get(k, 0) + (e - s)
        chips = len({c for c, *_ in self.ops}) or 1
        return [[k, v * 1e-9 / chips]
                for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """Idle device seconds in the window, summed by the innermost host
        span open at each gap's midpoint, largest first."""
        chips = sorted({c for c, *_ in self.ops})
        lo, hi = self.window
        tot: dict[str, int] = {}
        for c in chips or [None]:
            edges = [lo]
            for s, e in (self.busy_intervals(c) if c else []):
                edges += [s, e]
            edges.append(hi)
            for s, e in zip(edges[::2], edges[1::2]):
                if e <= s:
                    continue
                mid = (s + e) // 2
                open_ = [(ss, nm) for nm, ss, ee in self.spans
                         if ss <= mid < ee and nm != WINDOW]
                k = max(open_)[1] if open_ else WINDOW
                tot[k] = tot.get(k, 0) + (e - s)
        div = len(chips) or 1
        return [[k, v * 1e-9 / div]
                for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
