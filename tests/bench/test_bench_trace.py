"""The trace reducers against small traces whose answers are known."""
from types import SimpleNamespace as NS

import pytest

from bench.lib import trace
from bench.lib.trace import Evidence, Op

SORT = "%sort.25 = (s32[8,636104]{1,0:T(8,128)}, s32[8,636104]{1,0:T(8,128)}) sort(s32[8,636104]{1,0} %a, s32[8,636104]{1,0} %b), dimensions={1}, is_stable=true"
FUSION = "%fusion.5 = s32[5091328]{0:T(1024)S(1)} fusion(s32[8,524288]{1,0:T(8,128)S(1)} %x), kind=kCustom, calls=%fused_computation.5"
A2A = "%all-to-all.3 = u32[4,4,1024]{2,1,0} all-to-all(u32[4,4,1024]{2,1,0} %p), dimensions={0}"
AG_START = "%all-gather-start = (u32[8]{0}, u32[32]{0}) all-gather-start(u32[8]{0} %q), dimensions={0}"


@pytest.mark.parametrize(
    "text,want",
    [
        (SORT, "sort"),
        (FUSION, "fusion"),
        (A2A, "all-to-all"),
        (AG_START, "all-gather-start"),
        ("%copy-start.1 = (s32[8]{0}, s32[8]{0}, u32[]{:S(2)}) copy-start(s32[8]{0} %x)", "copy-start"),
        ("jit_run(1234)", "jit_run(1234)"),
        ("%while.13", "while"),
    ],
)
def test_opcode_of_hlo_text(text, want):
    assert trace.opcode(text) == want


def op(text, a, b):
    return Op(text, trace.opcode(text), a, b)


def two_chip_evidence():
    # window [100, 1100] ns on both chips; calls = 2
    ops = {
        0: [op(SORT, 100, 300), op(FUSION, 250, 400), op(A2A, 500, 600), op(SORT, 900, 1000)],
        1: [op(SORT, 50, 150), op(A2A, 500, 700), op(FUSION, 1050, 1300)],
    }
    async_ops = {0: [op(AG_START, 550, 650)], 1: []}
    host = [("window", 100, 1100), ("sort_call", 100, 600), ("result_wait", 600, 1100)]
    return Evidence(window=(100, 1100), ops=ops, async_ops=async_ops, host=host, calls=2)


def test_union_clips_and_merges():
    assert trace.union_ns([(0, 10), (5, 15), (20, 30)], 0, 100) == 25
    assert trace.union_ns([(0, 10), (5, 15), (20, 30)], 8, 25) == 12
    assert trace.union_ns([], 0, 1) == 0


def test_idle_share_is_one_minus_busy_over_window_mean_of_chips():
    ev = two_chip_evidence()
    # chip0 busy: [100,400] + [500,600] + [900,1000] = 500 of 1000
    # chip1 busy: [100,150] + [500,700] + [1050,1100] = 300 of 1000
    assert trace.mean_busy_s(ev) == pytest.approx(400e-9)
    assert trace.idle_share_pct(ev) == pytest.approx(60.0)


def test_sort_ms_per_call_sums_sort_ops_inside_the_window():
    ev = two_chip_evidence()
    # chip0: 200 + 100 = 300 ns; chip1: clipped [100,150] = 50 ns
    assert trace.opcode_ms_per_call(ev, ["sort"]) == pytest.approx((300 + 50) / 2 / 2 / 1e6)


def test_collective_ms_counts_async_and_sync_once():
    ev = two_chip_evidence()
    # chip0: a2a [500,600] ∪ all-gather in flight [550,650] = 150; chip1: 200
    assert trace.collective_ms_per_call(ev) == pytest.approx((150 + 200) / 2 / 2 / 1e6)


def test_readers_return_nothing_without_their_ops():
    ev = two_chip_evidence()
    ev.ops = {0: [op(FUSION, 100, 200)]}
    ev.async_ops = {}
    assert trace.opcode_ms_per_call(ev, ["sort"]) is None
    assert trace.collective_ms_per_call(ev) is None
    empty = Evidence(window=(0, 10), ops={}, async_ops={}, host=[("window", 0, 10)])
    assert trace.idle_share_pct(empty) is None and trace.mean_busy_s(empty) is None


def test_breakdown_names_gaps_by_the_host_spans():
    ev = two_chip_evidence()
    out = trace.breakdown(ev)
    names = [n for n, _ in out["device_ops"]]
    assert names[0] == "sort.25 (sort)"
    top = dict((n, s) for n, s in out["device_ops"])
    assert top["sort.25 (sort)"] == pytest.approx((300 + 50) / 2 / 1e9)
    gaps = out["idle_gaps"]
    # chip1's two longest gaps: [150,500] in sort_call, [700,1050] in result_wait
    assert ["chip1:sort_call", pytest.approx(350e-9)] in gaps[:2]
    assert ["chip1:result_wait", pytest.approx(350e-9)] in gaps[:2]
    assert ["chip0:sort_call", pytest.approx(100e-9)] in gaps
    assert all(a >= b for (_, a), (_, b) in zip(gaps, gaps[1:]))


def test_region_device_ms():
    ev = two_chip_evidence()
    ev.host.append(("lax_sort", 880, 1010))
    # chip0 busy in [880,1010]: [900,1000] = 100; chip1: none
    assert trace.region_device_ms(ev, "lax_sort") == pytest.approx(50 / 1e6)
    assert trace.region_device_ms(ev, "absent") is None


def _line(name, events):
    return NS(name=name, events=[NS(name=n, start_ns=a, duration_ns=d) for n, a, d in events])


def test_from_planes_reads_device_and_host_planes():
    planes = [
        NS(name="/device:TPU:0", lines=[
            _line("XLA Modules", [("jit_run(1)", 10, 100)]),
            _line("XLA Ops", [(SORT, 20, 30), (FUSION, 60, 10)]),
            _line("Async XLA Ops", [(AG_START, 40, 5)]),
        ]),
        NS(name="/device:TPU:1", lines=[_line("XLA Ops", [(A2A, 5, 5)])]),
        NS(name="/host:CPU", lines=[
            _line("python", [("window", 0, 200), ("sort_call", 0, 90), ("PjitFunction(run)", 1, 2)]),
            _line("main/287", [("CommonPjRtLoadedExecutable::Execute", 3, 4)]),
        ]),
        NS(name="/host:metadata", lines=[]),
    ]
    ops, async_ops, host, window = trace.from_planes(planes, chips=[0])
    assert window == (0, 200)
    assert [o.opcode for o in ops[0]] == ["sort", "fusion"]
    assert ops[0][0].end == 50 and list(ops) == [0]
    assert [o.opcode for o in async_ops[0]] == ["all-gather-start"]
    assert [h[0] for h in host] == ["window", "sort_call"]
    with pytest.raises(ValueError):
        trace.from_planes(planes[:2])
