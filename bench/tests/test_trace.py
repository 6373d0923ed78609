"""The trace reduction on a synthesised trace, checked by hand."""
import pytest

from bench import trace as trace_lib

MS = 1_000_000  # ns
KERNEL = '%{} = (f32[8]) custom-call(f32[8] %a), ' \
    'custom_call_target="tpu_custom_call"'


def _trace():
    # window [0, 100) ms; device ops (ms): a loop [10, 50) holding kernel
    # knn_merge_cand.1 [12, 20) and fusion.3 [20, 30); ne_forces_gather
    # [60, 80); a fusion [75, 90) overlapping it; an op outside the window
    ops = {0: [("while.1", 10 * MS, 50 * MS),
               (KERNEL.format("knn_merge_cand.1"), 12 * MS, 20 * MS),
               ("fusion.3", 20 * MS, 30 * MS),
               (KERNEL.format("ne_forces_gather_pallas"), 60 * MS, 80 * MS),
               ("fusion.7", 75 * MS, 90 * MS),
               ("fusion.9", 120 * MS, 130 * MS)]}
    spans = [("bench.window", 0, 100 * MS),
             ("bench.frame", 0, 55 * MS),
             ("bench.dispatch", 0, 5 * MS),
             ("bench.frame", 55 * MS, 100 * MS),
             ("bench.readback", 90 * MS, 100 * MS)]
    return trace_lib.Trace(ops, spans)


def test_union_and_busy():
    assert trace_lib.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == \
        [[0, 3], [5, 9]]
    assert trace_lib.union([(0, 10)], 2, 4) == [[2, 4]]
    tr = _trace()
    assert tr.busy(0) == [[10 * MS, 50 * MS], [60 * MS, 90 * MS]]


def test_summary_idle_share_and_kernels():
    s = _trace().summary()
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(0.07)
    assert s["idle_share"] == pytest.approx(0.3)
    # busy 70 ms minus kernels 8 + 20 ms
    assert s["outside_kernels_s"] == pytest.approx(0.042)


def test_outside_kernels_leaves_out_the_init_span():
    tr = _trace()
    # an init span over [0, 15) ms holding its own op [1, 14)
    tr.ops[0].append(("fusion.1", 1 * MS, 14 * MS))
    tr.spans.append(("bench.init", 0, 15 * MS))
    s = tr.summary()
    assert s["busy_s"] == pytest.approx(0.079)
    # busy outside the init span: [15, 50) + [60, 90) = 65 ms; kernels
    # outside it: [15, 20) + [60, 80) = 25 ms
    assert s["outside_kernels_s"] == pytest.approx(0.040)
    assert trace_lib.subtract([[0, 10], [20, 30]], [[5, 22], [25, 26]]) \
        == [[0, 5], [22, 25], [26, 30]]


def test_kernel_time_and_count():
    count, iv = _trace().kernel(0, "knn_merge_cand")
    assert count == 1 and trace_lib.length(iv) == 8 * MS


def test_top_ops_count_self_time():
    top = dict(_trace().top_ops(0))
    assert top["while.1"] == pytest.approx(0.040 - 0.008 - 0.010)
    assert top["knn_merge_cand.1"] == pytest.approx(0.008)
    assert top["ne_forces_gather_pallas"] == pytest.approx(0.020)
    # the overlapping fusion is not enclosed: its whole 15 ms counts
    assert top["fusion.7"] == pytest.approx(0.015)
    assert "fusion.9" not in top


def test_idle_gaps_named_by_innermost_span():
    tr = _trace()
    gaps = tr.idle_gaps(tr.busy(0))
    # [0, 10) in dispatch, [50, 60) in frame 1, [90, 100) in readback
    assert sorted(n for n, _ in gaps) == ["dispatch", "frame", "readback"]
    assert all(g == pytest.approx(0.010) for _, g in gaps)


def test_host_ms_per_frame_reader():
    from bench import common
    mod = common.load_module(common.BENCH / "metrics"
                             / "host_ms_per_frame.frames.py")

    class Run:
        trace = _trace()
    # frame 1: 55 ms wall, 40 busy -> 15; frame 2: 45 ms, 30 busy -> 15
    assert mod.read(Run()) == pytest.approx(15.0)


def test_roofline_share_reports_its_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    r = trace_lib.roofline_share(2.0, ops=100.0, nbytes=5.0, peaks=peaks)
    assert r == {"value": pytest.approx(50.0), "bound": "ops"}
    r = trace_lib.roofline_share(2.0, ops=1.0, nbytes=10.0, peaks=peaks)
    assert r == {"value": pytest.approx(50.0), "bound": "bytes"}


def _collective(name, op):
    """A collective's op event named as a TPU trace names it."""
    return f"%{name} = f32[8]{{0}} {op}(f32[8]{{0}} %a), " \
        "replica_groups={{0,1,2,3}}"


def test_collectives_pair_async_halves_and_merge_overlaps():
    # window [0, 100) ms on device 0: an all-gather in flight [10, 30)
    # (start [10, 11), done [28, 30)) overlapping another [20, 40); a
    # sync all-reduce named after the psum it lowers [50, 55) overlapping
    # a reduce-scatter [52, 60); a fusion, which is no collective
    ops = {0: [(_collective("all-gather-start.1", "all-gather-start"),
                10 * MS, 11 * MS),
               (_collective("all-gather-start.2", "all-gather-start"),
                20 * MS, 21 * MS),
               (_collective("all-gather-done.2", "all-gather-done"),
                38 * MS, 40 * MS),
               (_collective("all-gather-done.1", "all-gather-done"),
                28 * MS, 30 * MS),
               (_collective("psum.22", "all-reduce"), 50 * MS, 55 * MS),
               (_collective("reduce-scatter.3", "reduce-scatter"),
                52 * MS, 60 * MS),
               ("fusion.3", 60 * MS, 70 * MS)]}
    tr = trace_lib.Trace(ops, [("bench.window", 0, 100 * MS)])
    count, iv = tr.collectives(0)
    assert count == 4
    assert iv == [[10 * MS, 40 * MS], [50 * MS, 60 * MS]]


def test_collectives_pair_bare_names_in_order():
    # names without instruction text: a done pairs with the oldest open
    # start of its kind; a start with no done counts alone
    ops = {0: [("collective-permute-start.1", 0, 1 * MS),
               ("collective-permute-start.2", 2 * MS, 3 * MS),
               ("collective-permute-done.1", 5 * MS, 6 * MS),
               ("all-to-all.4", 7 * MS, 8 * MS)]}
    tr = trace_lib.Trace(ops, [("bench.window", 0, 100 * MS)])
    count, iv = tr.collectives(0)
    assert count == 3
    assert iv == [[0, 6 * MS], [7 * MS, 8 * MS]]


def test_collectives_keep_to_their_device_and_window():
    ops = {0: [(_collective("all-reduce.1", "all-reduce"), 90 * MS,
                110 * MS),
               (_collective("all-reduce.2", "all-reduce"), 120 * MS,
                130 * MS),
               (_collective("all-gather-start.3", "all-gather-start"),
                -20 * MS, -19 * MS),
               (_collective("all-gather-done.3", "all-gather-done"),
                4 * MS, 5 * MS)],
           1: [(_collective("all-reduce.1", "all-reduce"), 10 * MS,
                20 * MS)]}
    tr = trace_lib.Trace(ops, [("bench.window", 0, 100 * MS)])
    # device 0: the all-reduce across the window's end counts up to it,
    # the one after the window not at all, and the all-gather from
    # before the window from its start
    count, iv = tr.collectives(0)
    assert count == 2
    assert iv == [[0, 5 * MS], [90 * MS, 100 * MS]]
    assert tr.collectives(1) == (1, [[10 * MS, 20 * MS]])
    assert tr.collectives(2) == (0, [])
    # busy time counts collectives as any op: 11 ms and 10 ms
    assert tr.summary()["busy_s"] == pytest.approx((0.011 + 0.010) / 2)
