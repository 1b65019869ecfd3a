""".xplane.pb -> device busy and idle time, device time by program and by
operation, and the longest idle gaps with what the host was doing.

Reads the profiler's file with nothing but JAX
(jax.profiler.ProfileData). What a TPU v5e trace looks like (read by
hand, PR 27; PERF.md section 3 lists the names):
  * one plane per chip, "/device:TPU:<n>"; its line "XLA Modules" holds
    one event per program execution (name "jit_<fn>(<fingerprint>)"),
    "XLA Ops" the operations, each named by its whole HLO line
    ("%copy.105 = bf16[958,2,128,128]... copy(...)"), nested (a layer
    scan's `while` encloses its body's ops);
  * host threads are lines of the plane "/host:CPU" (PjRt's own spans,
    e.g. "PjitFunction(_decode_impl)", "shard_args").
Busy time is the union of the "XLA Ops" intervals; the window runs from
the first to the last event of the chips' and the host's planes; an
operation's own time is its duration minus its direct children's, so the
`while` that wraps 36 layers does not hide them."""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.harness.stats import union_length

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# host events that only say "a thread exists"
HOST_NOISE = ("ThreadpoolListener", "$")


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]


def self_times(events: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Own time (ns) per operation name from nested events of one line."""
    out: Dict[str, float] = {}
    stack: List[list] = []  # [name, end, own]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            n, _, own = stack.pop()
            out[n] = out.get(n, 0.0) + own
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    while stack:
        n, _, own = stack.pop()
        out[n] = out.get(n, 0.0) + own
    return out


def program_name(event_name: str) -> str:
    """"jit__decode_impl(1234)" -> "_decode_impl"."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def op_family(name: str) -> str:
    """"%fusion.123 = bf16[...] fusion(...)" -> "fusion"; a Pallas custom
    call keeps its kernel's name ("paged_attention_kernel")."""
    base = name.split(" ", 1)[0].lstrip("%")
    return re.sub(r"[._]\d+$", "", base)


def reduce_planes(planes: Sequence[dict], chips: int) -> Optional[dict]:
    """planes: [{"name", "lines": {line name: [(name, start_ns, dur_ns)]}}].
    Returns None when no device plane holds an operation."""
    devs = []
    for p in planes:
        m = DEVICE_PLANE.match(p["name"])
        if m and p["lines"].get(OPS_LINE):
            devs.append((int(m.group(1)), p))
    devs = [p for _, p in sorted(devs, key=lambda d: d[0])][:chips]
    if not devs:
        return None
    host = [p for p in planes if p["name"].startswith("/host:")]
    host_events = [
        (n, s, s + d) for p in host for evs in p["lines"].values()
        for n, s, d in evs if d > 0 and not n.startswith(HOST_NOISE)
    ]
    # The traced window is what the trace holds: from the first to the last
    # event of the chips' planes and of the host's, so a chip that idles at
    # either edge while the host works has that time counted as idle.
    spans = [(s, s + d) for p in devs for evs in p["lines"].values() for _, s, d in evs]
    spans += [(s, e) for _, s, e in host_events]
    window_ns = max(e for _, e in spans) - min(s for s, _ in spans)

    busy_ns, op_ns, prog_ns, prog_durs = [], {}, {}, {}
    for p in devs:
        ops = p["lines"][OPS_LINE]
        busy_ns.append(union_length((s, s + d) for _, s, d in ops))
        for name, own in self_times(ops).items():
            op_ns[name] = op_ns.get(name, 0.0) + own / len(devs)
        for name, _, d in p["lines"].get(MODULES_LINE, ()):
            prog = program_name(name)
            prog_ns[prog] = prog_ns.get(prog, 0.0) + d / len(devs)
            prog_durs.setdefault(prog, []).append(d)

    # idle gaps on the first chip, each named by the host event that
    # covers most of it
    ops0 = sorted((s, s + d) for _, s, d in devs[0]["lines"][OPS_LINE])
    gaps, end = [], ops0[0][0]
    for s, e in ops0:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    by_host: Dict[str, float] = {}
    for gs, ge in sorted(gaps, key=lambda g: g[0] - g[1])[:200]:
        best, best_ov = "no host span", 0.0
        for n, s, e in host_events:
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = n, ov
        by_host[best] = by_host.get(best, 0.0) + (ge - gs)

    def top(d: Dict[str, float], n: int = 10) -> list:
        return [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]

    fam: Dict[str, float] = {}
    for name, v in op_ns.items():
        fam[op_family(name)] = fam.get(op_family(name), 0.0) + v
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": window_ns / 1e9,
        "chips": len(devs),
        "device_ops": top(fam),
        "ops": op_ns,  # ns by full op name, mean over chips
        "programs": top(prog_ns, 32),
        "program_durations_ns": prog_durs,
        "idle_gaps": top(by_host),
        "gap_total_s": sum(e - s for s, e in gaps) / 1e9,
    }


def read_planes(path: str) -> List[dict]:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for pl in data.planes:
        lines: Dict[str, list] = {}
        for ln in pl.lines:
            lines.setdefault(ln.name, []).extend(_events(ln))
        planes.append({"name": pl.name, "lines": lines})
    return planes


def reduce_file(path: str, chips: int) -> Optional[dict]:
    return reduce_planes(read_planes(path), chips)
