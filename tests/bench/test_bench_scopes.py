"""Device time by superstep scope, from a small trace whose answers are known."""
import pytest

from bench.lib import harness, scopes
from bench.lib.trace import Evidence

from conftest import ROOT

SCOPED = {
    "ph2_local_sort_ms_per_call.bulk": "ph2_local_sort",
    "ph5_exchange_ms_per_call.bulk": "ph5_exchange",
    "ph6_merge_ms_per_call.bulk": "ph6_merge",
}


@pytest.mark.parametrize(
    "op_name,want",
    [
        ("jit(run)/vmap(ph5_exchange)/gather", "ph5_exchange"),
        ("jit(run)/jit(main)/vmap(jvp(ph2_local_sort))/sort", "ph2_local_sort"),
        ("jit(run)/shard_map(ph6_merge)/sort", "ph6_merge"),
        ("jit(run)/vmap(ph5_exchange)/ph6_merge/sort", "ph6_merge"),  # innermost wins
        ("jit(run)/vmap(ph3_splitters)/sort;jit(run)/vmap(ph4_partition)/while", "ph3_splitters"),
        ("jit(run)/vmap()/reduce_sum", None),
        ("jit(run)/vmap(ph5_exchanged)/gather", None),
        ("", None),
    ],
)
def test_scope_of_a_name_stack_path(op_name, want):
    assert scopes.scope_of(op_name) == want


def event(meta_id, start_ns, dur_ns):
    return f"events {{ metadata_id: {meta_id} offset_ps: {int(start_ns * 1000)} duration_ps: {int(dur_ns * 1000)} }}"


def line(line_id, name, events, first_id):
    """A text-format XLine, and the event metadata (ids from ``first_id``) its events name."""
    meta = {n: first_id + i for i, n in enumerate(sorted({n for n, *_ in events}))}
    evs = " ".join(event(meta[n], a, d) for n, a, d in events)
    return f'lines {{ id: {line_id} name: "{name}" timestamp_ns: 0 {evs} }}', meta


def plane(plane_id, name, lines):
    body, metas = [], {}
    for i, (lname, events) in enumerate(lines):
        text, meta = line(i + 1, lname, events, first_id=len(metas) + 1)
        body.append(text)
        metas.update(meta)
    meta_text = " ".join(f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}' for n, k in metas.items())
    return f'planes {{ id: {plane_id} name: "{name}" {" ".join(body)} {meta_text} }}'


def xspace(chips, host_events):
    """One TPU plane per chip (its program runs and their ops), one host plane."""
    planes = [
        plane(chip + 1, f"/device:TPU:{chip}", [("XLA Modules", modules), ("XLA Ops", ops)])
        for chip, (modules, ops) in chips.items()
    ]
    planes.append(plane(99, "/host:CPU", [("python", host_events)]))
    return "\n".join(planes)


SORT = "%sort.25 = s32[8]{0} sort(s32[8]{0} %a)"
GATHER = "%fusion.5 = s32[8]{0} fusion(s32[8]{0} %x), kind=kCustom"
SCATTER = "%fusion.7 = s32[8]{0} fusion(s32[8]{0} %y), kind=kCustom"
MERGE = "%sort.30 = s32[8]{0} sort(s32[8]{0} %b)"
COPY = "%copy-start = (s32[8]{0}, u32[]) copy-start(s32[8]{0} %c)"
PREPARE, ROUTE = "111", "222"
#: what xprof's hlo_stats names each (program, HLO op); the scatter has no name
NAMES = {
    (PREPARE, "sort.25"): "jit(run)/vmap(ph2_local_sort)/sort:",
    (ROUTE, "fusion.5"): "jit(run)/vmap(ph5_exchange)/gather:",
    (ROUTE, "fusion.7"): "",
    (ROUTE, "sort.30"): "jit(run)/vmap(ph6_merge)/sort:",
}

# window [100, 1100] ns, two calls; chip0 runs prepare [0, 200], then the
# route [200, 1300]; chip1 prepare [100, 300], route [300, 800]
CHIPS = {
    0: (
        [(f"jit_run({PREPARE})", 0, 200), (f"jit_run({ROUTE})", 200, 1100)],
        [
            (COPY, 0, 50),  # first op of a run, unnamed: stays unscoped
            (SORT, 50, 150),  # [50, 200]: 100 inside
            (GATHER, 200, 300),  # [200, 500]
            (SCATTER, 500, 100),  # [500, 600]: unnamed, after a ph5 op
            (MERGE, 600, 100),  # [600, 700]
            (SCATTER, 700, 50),  # [700, 750]: after a ph6 op
            (GATHER, 1000, 300),  # [1000, 1300]: 100 inside
        ],
    ),
    1: (
        [(f"jit_run({PREPARE})", 100, 200), (f"jit_run({ROUTE})", 300, 500)],
        [(SORT, 100, 200), (GATHER, 300, 200), (MERGE, 500, 300)],
    ),
}


def write_trace(root, text):
    import jax

    path = root / "plugins" / "profile" / "run" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    return path


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """Evidence of a run whose trace is the synthetic one above."""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(scopes, "hlo_op_names", lambda path: NAMES)
    write_trace(tmp_path, xspace(CHIPS, [("window", 100, 1000)]))
    return Evidence(window=(100, 1100), ops={0: [], 1: []}, async_ops={}, host=[], calls=2)


def test_ops_take_their_names_by_program_and_op(traced):
    ops = scopes.scoped_ops(traced)
    assert [(o.scope, o.named) for o in ops[0]] == [
        (None, False),
        ("ph2_local_sort", True),
        ("ph5_exchange", True),
        ("ph5_exchange", False),  # the scatter XLA left unnamed
        ("ph6_merge", True),
        ("ph6_merge", False),
        ("ph5_exchange", True),
    ]
    assert ops[1][2] == scopes.ScopedOp(MERGE, "ph6_merge", 500.0, 800.0, True)


def test_an_unnamed_op_takes_no_scope_across_program_runs(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(scopes, "hlo_op_names", lambda path: NAMES)
    modules = [(f"jit_run({PREPARE})", 0, 200), (f"jit_run({ROUTE})", 200, 300)]
    write_trace(tmp_path, xspace({0: (modules, [(SORT, 0, 100), (SCATTER, 200, 50)])}, [("window", 0, 500)]))
    ev = Evidence(window=(0, 500), ops={0: []}, async_ops={}, host=[], calls=1)
    assert [o.scope for o in scopes.scoped_ops(ev)[0]] == ["ph2_local_sort", None]


def test_scope_time_is_clipped_to_the_window_and_averaged(traced):
    # ph5: chip0 300 + 100 (scatter) + 100 (clipped) = 500, chip1 200 -> 350
    assert scopes.scope_ms_per_call(traced, "ph5_exchange") == pytest.approx(350 / 2 / 1e6)
    # ph2: chip0 100 (clipped), chip1 200
    assert scopes.scope_ms_per_call(traced, "ph2_local_sort") == pytest.approx(150 / 2 / 1e6)
    assert scopes.scope_ms_per_call(traced, "ph3_splitters") is None
    got = scopes.split(traced)
    assert got["scopes"] == pytest.approx(
        {"ph2_local_sort": 150e-9, "ph5_exchange": 350e-9, "ph6_merge": 225e-9}
    )
    # busy: chip0 [100, 750] + [1000, 1100] = 750, chip1 [100, 800] = 700;
    # the scatters (150 ns on chip0) are scoped, but not by their own name
    assert got["busy_s"] == pytest.approx(725e-9)
    assert got["coverage"] == pytest.approx(1.0)
    assert got["coverage_named"] == pytest.approx((725 - 75) / 725)
    top = scopes.top_ops(traced)
    assert top[0] == ["fusion.5", "ph5_exchange", pytest.approx(300e-9)]
    assert ["fusion.7", "ph5_exchange (after)", pytest.approx(50e-9)] in top


def test_only_the_chips_the_run_read_count(traced):
    traced.ops = {1: []}
    assert scopes.scope_ms_per_call(traced, "ph6_merge") == pytest.approx(300 / 2 / 1e6)


@pytest.mark.parametrize("name", sorted(SCOPED))
def test_scope_readers_on_the_synthetic_trace(traced, name):
    mod = harness.metric_reader(name, ROOT)
    want = scopes.scope_ms_per_call(traced, SCOPED[name])
    assert want is not None and mod.read(traced) == want


@pytest.mark.parametrize("name", sorted(SCOPED))
def test_scope_readers_return_nothing_for_a_program_without_scopes(tmp_path, monkeypatch, name):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(scopes, "hlo_op_names", lambda path: {k: "jit(run)/vmap()/sort:" for k in NAMES})
    write_trace(tmp_path, xspace(CHIPS, [("window", 100, 1000)]))
    ev = Evidence(window=(100, 1100), ops={0: [], 1: []}, async_ops={}, host=[], calls=2)
    assert harness.metric_reader(name, ROOT).read(ev) is None
    ev.ops = {}  # and a trace with no TPU plane the run read (the CPU's)
    assert harness.metric_reader(name, ROOT).read(ev) is None


def test_scope_readers_without_a_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "none")
    ev = Evidence(window=(0, 1), ops={0: []}, async_ops={}, host=[], calls=1)
    assert scopes.scoped_ops(ev) is None and scopes.split(ev) is None and scopes.top_ops(ev) == []
