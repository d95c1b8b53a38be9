"""The reduction from a profiler trace to device metrics, on small synthetic
traces whose answers are counted by hand."""

import pytest

from bench import trace as tr
from bench.harness import Reading

Op = tr.Op


def _ops():
    # device timeline (seconds): a while loop spanning two body ops, a
    # collective, a kernel, idle gaps at [0, 1), [4, 5) and [7, 10)
    return [
        Op("while.1", 1.0, 3.0),              # spans its body: not a leaf
        Op("fusion.2", 1.0, 1.5),
        Op("all-gather.3", 2.5, 1.5),
        Op("grouped_ffn.4", 5.0, 1.0),
        Op("grouped_ffn.4", 6.0, 0.5),
        Op("collective-permute-done.5", 6.25, 0.75),  # overlaps the kernel
    ]


def test_leaves_drop_spanning_ops():
    names = [o.name for o in tr.leaves(_ops())]
    assert "while.1" not in names and len(names) == 5


def test_busy_union_and_idle_share():
    ops = _ops()
    # union: [1, 4) + [5, 7) = 5 s of a 10 s window
    assert tr.busy_s(ops, 0.0, 10.0) == pytest.approx(5.0)
    # clipped to [2, 6): [2, 4) + [5, 6) = 3 s
    assert tr.busy_s(ops, 2.0, 6.0) == pytest.approx(3.0)
    r = Reading(cell=None, ops=ops, t0=0.0, t1=10.0, calls=2, work={},
                peaks={})
    assert r.idle_share() == pytest.approx(50.0)


def test_kernel_and_collective_time():
    ops = _ops()
    assert tr.kernel_s(ops, "grouped_ffn") == pytest.approx(1.5)
    assert tr.kernel_s(ops, "grouped") == 0.0      # whole names only
    # collectives: [2.5, 4) + [6.25, 7) = 2.25 s
    assert tr.collective_s(ops, 0.0, 10.0) == pytest.approx(2.25)
    # outside collectives: fusion [1, 2.5) + kernel [5, 6.5) = 3 s
    assert tr.local_s(ops, 0.0, 10.0) == pytest.approx(3.0)
    assert tr.op_name("%all-reduce.7 = f32[8]{0} all-reduce(...)") == (
        "all-reduce.7")
    assert tr.is_collective(Op("all-reduce.7", 0, 1))
    assert tr.is_collective(Op("fusion.9", 0, 1, category="collective"))
    assert not tr.is_collective(Op("fusion.9", 0, 1))


def test_top_ops_and_idle_gaps_named_by_host_span():
    ops = _ops()
    top = tr.top_ops(ops, k=3)
    assert sorted(n for n, _ in top) == ["all-gather.3", "fusion.2",
                                         "grouped_ffn.4"]
    assert [s for _, s in top] == [pytest.approx(1.5)] * 3
    assert tr.top_ops(ops)[-1] == ["collective-permute-done.5",
                                   pytest.approx(0.75)]
    host = [Op("bench.window", 0.0, 10.0), Op("bench.dispatch", 0.0, 1.0),
            Op("bench.wait", 1.0, 6.0), Op("bench.sample", 7.0, 3.0)]
    gaps = tr.idle_gaps(ops, host, 0.0, 10.0)
    assert gaps == [["bench.sample", pytest.approx(3.0)],
                    ["bench.dispatch", pytest.approx(1.0)],
                    ["bench.wait", pytest.approx(1.0)]]


def test_roofline_and_mfu_arithmetic():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    r = Reading(cell=None, ops=_ops(), t0=0.0, t1=10.0, calls=3,
                work={"tokens_per_call": 4, "chips": 2}, peaks=peaks)
    # per call: 20 flops -> 0.2 s, 1 byte -> 0.1 s; least 0.2 s x 3 calls
    # over 1.5 s of kernel time
    assert r.roofline("grouped_ffn", 20.0, 1.0) == pytest.approx(40.0)
    assert r.roofline("flash_attention", 20.0, 1.0) is None
    # 12 tokens in 10 s x 50 flops each over 2 chips x 100 flop/s
    assert r.mfu(50.0) == pytest.approx(100 * 1.2 * 50 / 200)
    assert Reading(cell=None, ops=[], t0=0, t1=1, calls=0, work={},
                   peaks=peaks).idle_share() is None
