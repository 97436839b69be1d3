"""Device time by program scope.

The served programs name their phases with ``jax.named_scope``:
``sling.push`` (the Horner push) and ``sling.select`` (top_k) in
``batched_topk``, ``sling.pair.fold`` (the row gathers and the sqrt(d)
fold) and ``sling.pair.join`` (``hp_join``) in
``pair_query_batch_pallas``. A profiler trace's op events carry no
scope, only the HLO instruction's text. So the scope of each
instruction is read from its module's optimized HLO text (as
``QueryEngine.program_texts()`` gives it): the innermost ``sling.*``
part of its ``metadata={op_name=...}``, or, for a fusion or call
without one, the one scope of the instructions it calls. The trace's
device time of each module is then summed by scope.
"""
from __future__ import annotations

import bisect
import re

PREFIX = "sling."
MIN_SHARE = 0.95        # a module mapped below this reads None

_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(
    r"\b(?:calls|to_apply|body|condition|branch_computations)="
    r"\{?((?:%?[\w.\-]+(?:,\s*)?)+)\}?")


def scope_of(op_name: str) -> str | None:
    """The innermost ``sling.*`` part of an op_name path."""
    parts = [p for p in op_name.split("/") if p.startswith(PREFIX)]
    return parts[-1] if parts else None


def scope_map(hlo_text: str) -> dict[str, str]:
    """Instruction name -> its ``sling.*`` scope, for every instruction
    of one optimized HLO module that has one."""
    own: dict[str, str | None] = {}
    calls: dict[str, list[str]] = {}
    body: dict[str, list[str]] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and "=" not in line.split("{", 1)[0]:
            comp = m.group(1)
            body[comp] = []
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        body[comp].append(name)
        op = _OP_NAME.search(line)
        own[name] = scope_of(op.group(1)) if op else None
        c = _CALLS.search(line)
        calls[name] = ([x.strip().lstrip("%") for x in c.group(1).split(",")]
                       if c else [])

    memo: dict[str, set] = {}

    def comp_scopes(c: str) -> set:
        if c not in memo:
            memo[c] = set()                     # guards a cycle
            found = set()
            for name in body.get(c, []):
                found |= instr_scopes(name)
            memo[c] = found
        return memo[c]

    def instr_scopes(name: str) -> set:
        if own.get(name):
            return {own[name]}
        found = set()
        for c in calls.get(name, []):
            found |= comp_scopes(c)
        return found

    out = {}
    for name in own:
        s = instr_scopes(name)
        if len(s) == 1:
            out[name] = next(iter(s))
    return out


def module_maps(texts) -> dict[str, dict[str, str]]:
    """``(jitted name, HLO text)`` pairs -> per module name, the merged
    scope map; an instruction name that two programs of one name map
    to different scopes is left out."""
    out: dict[str, dict[str, str]] = {}
    clash: dict[str, set] = {}
    for name, text in texts:
        m = out.setdefault(name, {})
        for instr, scope in scope_map(text).items():
            if m.get(instr, scope) != scope:
                clash.setdefault(name, set()).add(instr)
            m[instr] = scope
    for name, instrs in clash.items():
        for instr in instrs:
            out[name].pop(instr, None)
    return out


def instr_name(event_name: str) -> str | None:
    """``%fusion.47 = f32[8,7115]{...} fusion(...)`` -> ``fusion.47``."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else None


def device_time(path: str, maps: dict[str, dict[str, str]],
                window_name: str = "bench.window") -> dict:
    """Per module of the trace's TPU planes, inside the host
    annotation ``window_name`` (the whole trace where there is none):
    ``count`` executions, ``ops_s`` device seconds of its operations,
    ``scopes`` {scope: seconds} and ``mapped`` (the share of ``ops_s``
    that some scope holds)."""
    from jax.profiler import ProfileData

    from bench.trace import module_key
    pd = ProfileData.from_file(path)
    lo, hi = float("-inf"), float("inf")
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == window_name:
                        lo, hi = ev.start_ns, ev.start_ns + ev.duration_ns
    out: dict[str, dict] = {}
    for plane in pd.planes:
        if not (plane.name.startswith("/device:")
                and "TPU" in plane.name.upper()):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        execs = sorted((e.start_ns, e.start_ns + e.duration_ns,
                        module_key(e.name))
                       for e in lines.get("XLA Modules", [])
                       if lo <= e.start_ns < hi)
        starts = [x[0] for x in execs]
        for s, _e, key in execs:
            m = out.setdefault(key, {"count": 0, "ops_s": 0.0,
                                     "scopes": {}})
            m["count"] += 1
        for ev in lines.get("XLA Ops", []):
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            if i < 0 or ev.start_ns >= execs[i][1]:
                continue
            key = execs[i][2]
            m = out[key]
            secs = ev.duration_ns * 1e-9
            m["ops_s"] += secs
            scope = maps.get(key, {}).get(instr_name(ev.name))
            if scope is not None:
                m["scopes"][scope] = m["scopes"].get(scope, 0.0) + secs
    for m in out.values():
        m["mapped"] = (sum(m["scopes"].values()) / m["ops_s"]
                       if m["ops_s"] else 0.0)
    return out


def scope_ms(times: dict, word: str, scope: str):
    """Device ms per execution spent in ``scope`` by the modules whose
    name holds ``word``; None where any of them maps less than
    :data:`MIN_SHARE` of its device time, or none ran."""
    mods = [m for k, m in times.items() if word in k]
    count = sum(m["count"] for m in mods)
    if not count or any(m["mapped"] < MIN_SHARE for m in mods):
        return None
    return 1e3 * sum(m["scopes"].get(scope, 0.0) for m in mods) / count
