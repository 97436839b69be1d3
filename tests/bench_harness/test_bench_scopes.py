"""bench/scopes.py: the ``sling.*`` scope of each instruction of the
served programs, read from their optimized HLO, and device time summed
by scope."""
import pytest
from bench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from bench import scopes


@pytest.fixture(scope="module")
def cpu_texts():
    """The two served programs compiled for the CPU at a small size:
    the top-k program as an engine that served it gives it
    (``QueryEngine.program_texts``), the pair kernel interpreted."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import build
    from repro.graph import generators
    from repro.kernels.hp_join.ops import pair_query_batch_pallas
    from repro.serve import EngineConfig, QueryEngine
    g = generators.barabasi_albert(60, 3, seed=1, directed=False)
    idx = build.build_index(g, eps=0.2, exact_d=True, seed=0)
    eng = QueryEngine(idx, g, EngineConfig(source_batch=4, k_buckets=(4,)))
    eng.topk(np.arange(3), 4)
    texts = dict(eng.program_texts())
    S = jax.ShapeDtypeStruct
    n, w, ids = 64, 128, S((8,), jnp.int32)
    pair = pair_query_batch_pallas.lower(
        S((n, w), jnp.int32), S((n, w), jnp.float32), S((n,), jnp.float32),
        ids, ids, n=n, interpret=True)
    texts["pair_query_batch_pallas"] = pair.compile().as_text()
    return texts


def test_every_scope_of_the_served_programs_is_found(cpu_texts):
    want = {"batched_topk": {"sling.push", "sling.select"},
            "pair_query_batch_pallas": {"sling.pair.fold",
                                        "sling.pair.join"}}
    assert set(want) <= set(cpu_texts)
    for name, scope_set in want.items():
        assert set(scopes.scope_map(cpu_texts[name]).values()) == scope_set


def test_innermost_sling_part_of_an_op_name():
    assert scopes.scope_of(
        "jit(f)/jit(g)/sling.push/scatter-add") == "sling.push"
    assert scopes.scope_of(
        "jit(f)/sling.pair.join/jit(hp_join)/pallas_call") \
        == "sling.pair.join"
    assert scopes.scope_of("sling.a/sling.b/add") == "sling.b"
    assert scopes.scope_of("jit(f)/gather") is None


HLO = """\
HloModule jit_f, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%region_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b), metadata={op_name="scatter-add"}
}

%fused_computation.2 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %mul.3 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(f)/sling.push/mul"}
}

%fused_computation.5 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  %neg.1 = f32[4]{0} negate(%param_0.1), metadata={op_name="jit(f)/sling.push/neg"}
  ROOT %top.1 = f32[4]{0} sort(%neg.1), to_apply=%region_add, metadata={op_name="jit(f)/sling.select/sort"}
}

ENTRY %main.9 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion.2 = f32[4]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation.2
  %fusion.5 = f32[4]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.5
  ROOT %copy.7 = f32[4]{0} copy(%fusion.5), metadata={op_name="jit(f)/sling.select/copy"}
}
"""


def test_a_fusion_without_metadata_takes_the_one_scope_it_calls():
    m = scopes.scope_map(HLO)
    assert m["fusion.2"] == "sling.push"
    assert m["copy.7"] == "sling.select"
    # two scopes inside: left unmapped, as the parameter is
    assert "fusion.5" not in m and "x.1" not in m
    assert scopes.instr_name(
        "%fusion.2 = f32[4]{0:T(128)} fusion(f32[4]{0} %x.1), kind=kLoop") \
        == "fusion.2"


def test_programs_of_one_name_merge_and_drop_clashes():
    other = HLO.replace('sling.select/copy', 'sling.push/copy')
    m = scopes.module_maps([("f", HLO), ("f", other), ("g", other)])
    assert "copy.7" not in m["f"] and m["g"]["copy.7"] == "sling.push"
    assert m["f"]["fusion.2"] == "sling.push"


def test_scope_ms_reads_none_below_the_coverage_floor():
    times = {"batched_topk": {"count": 2, "ops_s": 1.0, "mapped": 0.96,
                              "scopes": {"sling.push": 0.9,
                                         "sling.select": 0.06}},
             "pair_query_batch_pallas": {"count": 4, "ops_s": 1.0,
                                         "mapped": 0.9,
                                         "scopes": {"sling.pair.fold": 0.9}}}
    assert scopes.scope_ms(times, "topk", "sling.push") == \
        pytest.approx(450.0)
    assert scopes.scope_ms(times, "pair", "sling.pair.fold") is None
    assert scopes.scope_ms(times, "source", "sling.push") is None
    assert scopes.scope_ms({}, "topk", "sling.push") is None


# ----------------------------------------------------------------------
# recorded on one v5e chip: a 0.3-s traced window of each wikivote cell
# with the program's span recorder on (bench/program_run.py --keep),
# and the optimized HLO of the program that window ran
# ----------------------------------------------------------------------
DATA = ROOT / "tests" / "bench_harness" / "data"
RECORDED = {"pair": ("pair_query_batch_pallas", "sling.pair.fold"),
            "topk": ("batched_topk", "sling.push")}


def _recorded(cell):
    import gzip
    module, _ = RECORDED[cell]
    with gzip.open(DATA / f"wikivote_{cell}.{module}.hlo.txt.gz", "rt") as f:
        text = f.read()
    path = str(DATA / f"wikivote_{cell}.xplane.pb")
    return path, scopes.device_time(path, scopes.module_maps(
        [(module, text)]))


def _op_seconds(path, module, pattern):
    """Device seconds of the window's ops of ``module`` whose HLO text
    matches ``pattern``, and the module's executions: a count that
    does not go through the scope map."""
    import re

    from jax.profiler import ProfileData

    from bench import trace
    pd = ProfileData.from_file(path)
    lo, hi = next((e.start_ns, e.start_ns + e.duration_ns)
                  for p in pd.planes if p.name.startswith("/host:")
                  for line in p.lines for e in line.events
                  if e.name == trace.WINDOW)
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    lines = {line.name: list(line.events) for line in plane.lines}
    execs = [(e.start_ns, e.start_ns + e.duration_ns)
             for e in lines["XLA Modules"]
             if lo <= e.start_ns < hi and trace.module_key(e.name) == module]
    secs = sum(e.duration_ns for e in lines["XLA Ops"]
               if re.search(pattern, e.name)
               and any(s <= e.start_ns < t for s, t in execs)) * 1e-9
    return secs, len(execs)


@pytest.mark.parametrize("cell", ["pair", "topk"])
def test_recorded_scopes_cover_the_program(cell):
    _, times = _recorded(cell)
    module, _ = RECORDED[cell]
    assert times[module]["count"] > 0
    assert times[module]["mapped"] >= scopes.MIN_SHARE


@pytest.mark.parametrize("cell,pattern", [
    # the push's 30 scatter fusions: (8, 7115) frontier from the edges
    ("topk", r"^%\S+ = f32\[8,7115\]\S* fusion\(.*s32\[129664\]"),
    # the fold's two sqrt(d) gathers: 256 x 640 rows from d's 7115
    ("pair", r"^%\S+ = f32\[163840\]\S* fusion\(.*f32\[7115\]"),
])
def test_recorded_scope_time_matches_its_ops(cell, pattern):
    path, times = _recorded(cell)
    module, scope = RECORDED[cell]
    secs, execs = _op_seconds(path, module, pattern)
    want = 1e3 * secs / execs
    got = scopes.scope_ms(times, cell, scope)
    assert got == pytest.approx(want, rel=0.05)


@pytest.mark.parametrize("cell", ["pair", "topk"])
def test_recorded_idle_gaps_are_named_by_program_spans(cell):
    """Every long gap that a program span overlaps is named by one; a
    span open when the profiler started or stopped is not in the trace,
    so a gap at the window's edge may have none."""
    from bench import program
    path, _ = _recorded(cell)
    out = program.idle(path)
    tl = program.timeline(path)
    lo, hi = tl["window"]
    gaps = sorted(program.gaps(tl["busy"][0], lo, hi),
                  key=lambda g: g[0] - g[1])[:10]
    assert [g for _, g in out["idle_gaps"]] == pytest.approx(
        [(e - s) * 1e-9 for s, e in gaps])
    for (label, _), (s, e) in zip(out["idle_gaps"], gaps):
        overlapped = any(hs < e and he > s and name.startswith("sling.")
                         for hs, he, name in tl["host"])
        assert label.startswith("host: sling.") == overlapped, label
    idle = (hi - lo - sum(e - s for s, e in tl["busy"][0])) * 1e-9
    assert sum(out["idle_by_span"].values()) == pytest.approx(idle)
    assert sum(label.startswith("host: sling.")
               for label, _ in out["idle_gaps"]) >= 8
