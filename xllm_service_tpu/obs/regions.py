"""Device time by region of a step program (docs/OBSERVABILITY.md "Device
regions").

A profile's `XLA Ops` events carry no `jax.named_scope` (PERF.md, PR 28),
but each is named by its HLO instruction, and the COMPILED program's text
keeps the scope in that instruction's `metadata={op_name="..."}`. So the
join goes through the text: `parse_regions` reads `{op key: region}` out
of one compiled program, `attribute` sums a profile's own nanoseconds by
op name over the maps of the programs that ran.

An op's key is its instruction name and result shape without layouts
("fusion.78 bf16[32,3072]"): the trace prints operand shapes and the text
does not, so whole lines never match. One trace holds several step
programs and their instruction numbering is independent: a key that two
programs give different regions is `ambiguous`, never guessed; a key no
program has, or whose instruction carries no scope, is `unnamed`.

No JAX at import: the master imports `obs`."""

from __future__ import annotations

import re
import weakref
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

from xllm_service_tpu.obs.spans import DEVICE_REGIONS, REGION_SCOPE

UNNAMED, AMBIGUOUS = "unnamed", "ambiguous"

_SCOPE = re.compile(re.escape(REGION_SCOPE) + r"([a-z_]+)")
_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_NAME = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
# computations an instruction RUNS as ops of their own (a fusion's `calls`
# and a reducer's `to_apply` are part of the instruction itself)
_RUNS = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)"
)
_TO_APPLY = re.compile(r"\bto_apply=%?([\w.\-]+)")  # of a `call` only
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_MATMULS = ("convolution", "dot")
# what only hands values on: a region does not spread through these
_STRUCTURAL = ("tuple", "get-tuple-element", "parameter", "while", "conditional", "call")


def scope_region(op_name: str) -> Optional[str]:
    """The innermost `xllm.<region>` of an `op_name`, or None."""
    for name in reversed(_SCOPE.findall(op_name)):
        if name in DEVICE_REGIONS:
            return name
    return None


def _balanced(s: str, i: int) -> int:
    """Index just past the group that opens at s[i] ('(' or '{')."""
    open_, close = s[i], ")" if s[i] == "(" else "}"
    depth = 0
    for j in range(i, len(s)):
        if s[j] == open_:
            depth += 1
        elif s[j] == close:
            depth -= 1
            if depth == 0:
                return j + 1
    return len(s)


def _split(line: str) -> Optional[Tuple[str, str, str, str, str]]:
    """One HLO instruction line (of the compiled text or of a trace event's
    name) -> (name, result shape without layouts, opcode, operands, rest)."""
    m = _NAME.match(line)
    if not m:
        return None
    rest = line[m.end():]
    end = _balanced(rest, 0) if rest.startswith("(") else rest.find(" ")
    if end <= 0:
        return None
    shape = re.sub(r"\{[^{}]*\}", "", rest[:end])
    op = _OPCODE.match(rest, end)
    if not op:
        return m.group(1), shape, "", "", ""
    close = _balanced(rest, op.end() - 1)
    return m.group(1), shape, op.group(1), rest[op.end():close - 1], rest[close:]


def op_key(name: str) -> str:
    """The join key of an op, from its line in the compiled text or from
    the name a profile gives its event."""
    parts = _split(name)
    if parts is None:
        return name.strip().lstrip("%")
    return f"{parts[0]} {parts[1]}"


class _Instr:
    __slots__ = ("key", "opcode", "operands", "calls", "runs", "region")

    def __init__(self, key, opcode, operands, calls, runs, region):
        self.key, self.opcode, self.operands = key, opcode, operands
        self.calls, self.runs, self.region = calls, runs, region


def _computations(hlo_text: str):
    """({computation: {instruction: _Instr}}, entry computation's name)."""
    comps: Dict[str, Dict[str, _Instr]] = {}
    entry, cur = None, None
    for line in hlo_text.splitlines():
        if cur is None:
            h = _HEADER.match(line)
            if h:
                cur = comps.setdefault(h.group(1), {})
                if line.startswith("ENTRY"):
                    entry = h.group(1)
            continue
        if line.startswith("}"):
            cur = None
            continue
        parts = _split(line)
        if parts is None or not parts[2]:
            continue
        name, shape, opcode, operands, rest = parts
        calls = _CALLS.search(rest)
        runs = _RUNS.findall(rest)
        for b in _BRANCHES.findall(rest):
            runs += _REF.findall(b)
        if opcode == "call":
            runs += _TO_APPLY.findall(rest)
        meta = _OP_NAME.search(rest)
        cur[name] = _Instr(
            f"{name} {shape}", opcode, _REF.findall(operands),
            calls.group(1) if calls else None, runs,
            scope_region(meta.group(1)) if meta else None,
        )
    return comps, entry


def _matmul_region(comps, comp: Optional[str], depth: int = 0) -> Optional[str]:
    """The region of the first convolution or dot (with a region) that a
    fused computation holds, itself or in a fusion nested in it."""
    body = comps.get(comp or "")
    if body is None or depth > 8:
        return None
    for ins in body.values():
        if ins.opcode in _MATMULS and ins.region:
            return ins.region
    for ins in body.values():
        if ins.calls:
            got = _matmul_region(comps, ins.calls, depth + 1)
            if got:
                return got
    return None


def _fused_region(comps, comp: Optional[str]) -> Optional[str]:
    """The region most instructions of a fused computation name."""
    votes: Dict[str, int] = {}
    for ins in comps.get(comp or "", {}).values():
        if ins.region:
            votes[ins.region] = votes.get(ins.region, 0) + 1
    return max(votes, key=votes.get) if votes else None


def parse_regions(hlo_text: str) -> Dict[str, str]:
    """`compiled.as_text()` -> {op key: region} for every instruction that
    runs as an op of its own (the entry computation's and, from there, the
    loops' and branches'; not the insides of a fusion).

    * an instruction's region is the innermost `xllm.<region>` of its
      `op_name`;
    * a fusion's own metadata is its root's: where its fused computation
      holds a convolution or a dot, the fusion belongs to THAT
      instruction's region (a matmul that swallowed the norm before it or
      the residual add after it stays a matmul); a fusion with no
      metadata at all (a multi-output fusion's root is a bare tuple) takes
      the region most of its fused instructions name;
    * an instruction with no region takes its consumers' where the
      computation shows some and they all agree (a layout `copy` feeding
      one fusion, a `copy-done` feeding three of one region); failing
      that, the one region that its neighbours with a region (consumers
      and operands) all name; else it is left out (`attribute` calls it
      `unnamed`). Between two regions nothing is guessed."""
    comps, entry = _computations(hlo_text)
    out: Dict[str, str] = {}
    for body in _executed(comps, entry):
        region: Dict[str, Optional[str]] = {}
        consumers: Dict[str, List[str]] = {name: [] for name in body}
        for name, ins in body.items():
            region[name] = ins.region
            if ins.calls:
                region[name] = (
                    _matmul_region(comps, ins.calls) or ins.region
                    or _fused_region(comps, ins.calls)
                )
            for o in ins.operands:
                if o in body:
                    consumers[o].append(name)
        # the consumers' region first (a layout copy belongs to what reads
        # it), then that of every neighbour that has one (the cumulative
        # sum that XLA rewrites into bare reduce-windows in mid-sampler)
        _settle(region, consumers, all_known=True)
        neighbours = {
            name: consumers[name] + [o for o in ins.operands if o in body]
            for name, ins in body.items() if ins.opcode not in _STRUCTURAL
        }
        _settle(region, neighbours, all_known=False)
        for name, ins in body.items():
            if region[name] is not None:
                out[ins.key] = region[name]
    return out


def _executed(comps, entry):
    """The bodies of the computations whose instructions run as ops of
    their own: the entry computation and, from there, the loops' bodies
    and conditions and the branches."""
    seen, todo = set(), [entry]
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for ins in comps[comp].values():
            todo.extend(ins.runs)
        yield comps[comp]


def _settle(region, around, all_known: bool) -> None:
    """Give each instruction without a region the one region that the
    instructions `around` it name (all of them, or those that have one),
    until nothing moves: a copy-start under a copy-done under a fusion."""
    moved = True
    while moved:
        moved = False
        for name, others in around.items():
            if region[name] is not None:
                continue
            got = {region[u] for u in others}
            if not all_known:
                got.discard(None)
            if len(got) == 1 and None not in got:
                region[name], moved = got.pop(), True


def merge_maps(maps: Iterable[Mapping[str, str]]) -> Dict[str, str]:
    """One {op key: region} over several programs' maps: a key they
    disagree on is AMBIGUOUS."""
    merged: Dict[str, str] = {}
    for m in maps:
        for key, region in m.items():
            if merged.setdefault(key, region) != region:
                merged[key] = AMBIGUOUS
    return merged


def assign(op_names: Iterable[str], maps: Iterable[Mapping[str, str]]) -> Dict[str, str]:
    """{op name as the profile has it: region, UNNAMED or AMBIGUOUS}."""
    merged = merge_maps(maps)
    return {name: merged.get(op_key(name), UNNAMED) for name in op_names}


def attribute(op_ns: Mapping[str, float], maps: Iterable[Mapping[str, str]]) -> Dict[str, float]:
    """Own nanoseconds by op name (a profile's `XLA Ops`, reduced) ->
    nanoseconds by region, with UNNAMED and AMBIGUOUS beside the regions:
    the values sum to the input's."""
    out: Dict[str, float] = {}
    for name, region in assign(op_ns, maps).items():
        out[region] = out.get(region, 0.0) + op_ns[name]
    return out


# The executors alive in this process, held weakly (the tests build
# hundreds): a caller with no handle on one (the benchmark's readers, an
# operator's console) asks here for the maps of their step programs.
_EXECUTORS: "weakref.WeakSet" = weakref.WeakSet()


def register(executor) -> None:
    """An object with `program_regions(budget_s) -> {program: [map, ...]}`."""
    _EXECUTORS.add(executor)


def program_maps(budget_s: Optional[float] = None) -> Dict[str, List[Dict[str, str]]]:
    """{step program's function name: its maps, one per shape it lowered}
    over every registered executor alive. May compile, within `budget_s`
    an executor (not the serving path): ModelExecutor.program_regions."""
    out: Dict[str, List[Dict[str, str]]] = {}
    for ex in list(_EXECUTORS):
        for program, maps in ex.program_regions(budget_s).items():
            out.setdefault(program, []).extend(maps)
    return out


def all_maps(programs: Mapping[str, Sequence[Mapping[str, str]]]) -> List[Mapping[str, str]]:
    return [m for maps in programs.values() for m in maps]
