"""The reduction from a trace to metrics."""
import numpy as np

from harness.trace import Reduced, gaps, union_length


def test_union_counts_overlaps_once():
    iv = np.array([[0.0, 2.0], [1.0, 3.0], [5.0, 6.0], [5.5, 5.7]])
    assert union_length(iv) == 4.0
    assert union_length(np.zeros((0, 2))) == 0.0


def test_gaps_between_busy_intervals():
    iv = np.array([[5.0, 6.0], [0.0, 2.0], [1.0, 3.0], [6.0, 7.0]])
    np.testing.assert_array_equal(gaps(iv), [[3.0, 5.0]])


def test_reduced_shares():
    r = Reduced(window_s=10.0, ops={0: [("fused_snn_net.1", 0.0, 4.0),
                                        ("fusion.3", 3.0, 5.0),
                                        ("copy.2", 7.0, 8.0)]},
                host=[("step", 4.5, 7.5), ("outer", 0.0, 10.0)])
    assert r.busy_s == 6.0
    assert r.op_seconds(r"fused_snn_net") == 4.0
    assert r.busy_outside_s(r"fused_snn_net") == 2.0
    assert r.top_ops(2) == [["fused_snn_net.1", 4.0], ["fusion.3", 2.0]]
    assert r.idle_gaps() == [["step", 2.0]]


def test_recorded_chip_trace():
    """A trace of two mnist-batch calls on one TPU v5 lite (first chip
    run of the benchmark). The numbers were cross-checked once by a
    brute-force 1 ns rasterisation of the same events."""
    from pathlib import Path

    from harness.trace import read_xplane
    path = Path(__file__).parent / "data" / "mnist-batch.xplane.pb"
    r = read_xplane(str(path), window_s=0.0929217180000137)
    kernel = r"^%fused_snn_net(\.\d+)?$"
    assert list(r.ops) == [0] and len(r.ops[0]) == 328
    assert abs(r.busy_s - 0.089034562) < 1e-12
    assert abs(r.op_seconds(kernel) - 0.066097009) < 1e-12
    assert abs(r.busy_outside_s(kernel) - 0.022937553) < 1e-12
    assert [n for n, _ in r.top_ops(2)] == ["%fused_snn_net.3",
                                            "%fused_snn_net.4"]
