"""The readers of the program's layer counters, on synthetic snapshots."""
from types import SimpleNamespace

import pytest

from bench import spec
from bench.run import RunData


def _run(batcher, engines, acked=0, failed=0):
    reqs = [SimpleNamespace(kind="delta", idx=i)
            for i in range(acked + failed)]
    reqs.append(SimpleNamespace(kind="solve", idx=acked + failed))
    outcomes = {q.idx: SimpleNamespace(ok=q.idx < acked or q.kind == "solve")
                for q in reqs}
    counters = tuple({"batcher": b, "engines": e}
                     for b, e in zip(batcher, engines))
    return RunData(reqs, outcomes, 0.0, counters, None, None, None, {}, 8)


def _batcher(requests, wait):
    return {"sweeps": 1, "requests": requests, "queue_wait_s": wait}


def _engines(a, b):
    return {"t0": {"ingest_host_s": a}, "t1": {"ingest_host_s": b}}


OLD = ({"sweeps": 1, "requests": 10}, {"sweeps": 2, "requests": 14})
ENG = (_engines(0.5, 0.0), _engines(0.9, 0.1))

CASES = [
    # name, batcher snapshots, engine snapshots, acked, failed, expected
    ("batcher.queue_wait_ms", (_batcher(10, 1.0), _batcher(14, 1.02)),
     ENG, 0, 0, 5.0),
    ("batcher.queue_wait_ms", (_batcher(10, 1.0), _batcher(10, 1.0)),
     ENG, 0, 0, None),
    ("batcher.queue_wait_ms", OLD, ENG, 0, 0, None),
    ("batcher.queue_wait_ms.median", (_batcher(0, 0.0), _batcher(4, 0.002)),
     ENG, 0, 0, 0.5),
    ("batcher.queue_wait_ms.median", (_batcher(3, 0.1), _batcher(3, 0.1)),
     ENG, 0, 0, None),
    # 0.5 s over two ACKed deltas; the refused one does not count.
    ("engine.ingest_host_ms", OLD, ENG, 2, 1, 250.0),
    ("engine.ingest_host_ms", OLD, ENG, 0, 2, None),
    ("engine.ingest_host_ms", OLD, ({"t0": {}}, {"t0": {}}), 2, 0, None),
]


@pytest.mark.parametrize("name,batcher,engines,acked,failed,expected", CASES)
def test_layer_counter_readers(name, batcher, engines, acked, failed,
                               expected):
    value = spec.metric_reader(name).read(
        _run(batcher, engines, acked, failed))
    if expected is None:
        assert value is None
    else:
        assert value == pytest.approx(expected)


def _full(dispatcher, pool, batcher, engines):
    return {"dispatcher": dispatcher, "pool": pool, "batcher": batcher,
            "engines": engines}


NEW = (_full({"frames_handled": 10, "decode_s": 0.0, "encode_s": 0.0,
              "solve_frames": 8, "fetch_s": 0.0, "fetch_max_s": 0.0,
              "gc": {"pauses": 1, "pause_s": 0.001, "pause_max_s": 0.001}},
             {"lock_wait_s": 0.0, "lock_waits": 0, "lock_wait_max_s": 0.0,
              "journal": {"appends": 0, "append_s": 0.0, "fsync_s": 0.0}},
             _batcher(10, 1.0) | {"sweep_s": 0.0}, _engines(0.5, 0.0)),
       _full({"frames_handled": 14, "decode_s": 0.001, "encode_s": 0.003,
              "solve_frames": 10, "fetch_s": 0.006, "fetch_max_s": 0.004,
              "gc": {"pauses": 3, "pause_s": 0.004, "pause_max_s": 0.002}},
             {"lock_wait_s": 0.002, "lock_waits": 2, "lock_wait_max_s": 0.0015,
              "journal": {"appends": 2, "append_s": 0.004, "fsync_s": 0.003}},
             _batcher(14, 1.02) | {"sweeps": 3, "sweep_s": 0.01},
             _engines(0.9, 0.1)))
# The parent program: the summaries without this layer's counters.
BARE = (_full({"frames_handled": 10}, {"journaled": True}, OLD[0],
              {"t0": {}}),
        _full({"frames_handled": 14}, {"journaled": True}, OLD[1],
              {"t0": {}}))

LAYERS = {"lock_wait_ms": 1.0, "journal_append_ms": 2.0,
          "journal_fsync_share": 0.75, "ingest_host_ms": 250.0,
          "codec_ms": 1.0, "queue_wait_ms": 5.0, "sweep_ms": 5.0,
          "fetch_ms": 3.0, "gc_pause_ms": 3.0, "gc_pauses": 2}


@pytest.mark.parametrize("program", ["current", "without_counters"])
def test_layer_split_of_a_window(program):
    from bench import layers

    counters = NEW if program == "current" else BARE
    run = _run(OLD, ENG, acked=2, failed=1)
    run.counters = counters
    out = layers.layers(run)
    assert out["acked_deltas"] == 2
    for key, value in LAYERS.items():
        if program == "current":
            assert out[key] == pytest.approx(value), key
        else:
            assert out[key] is None, key
    if program == "current":
        assert out["max_s"] == {"fetch": 0.004, "queue_wait": None,
                                "lock_wait": 0.0015, "gc_pause": 0.002}
        assert out["window_deltas"]["pool"]["journal.appends"] == 2


def _traced_run(per_chip_s, planes=4, solves=150, replies=4000, d=16384):
    """A run whose 3 s trace holds ``solves`` executions of the sharded
    solve program on each of ``planes`` device planes, ``per_chip_s``
    device seconds on each, and ``replies`` answered SOLVEs counted by the
    host clock (more: the trace's write-out outlasts it)."""
    from bench.trace import Reduced

    name = "jit__local_tri_solve"
    trace = Reduced(busy_s=0.5, window_s=3.0,
                    modules={name: planes * per_chip_s, "jit_other": 0.1},
                    module_counts={name: planes * solves, "jit_other": 1},
                    ops={}, op_counts={}, gaps={}, devices=planes,
                    module_planes={name: planes, "jit_other": 1})
    reqs = [SimpleNamespace(kind="solve", idx=i, tenant="silo")
            for i in range(replies)]
    outcomes = {i: SimpleNamespace(ok=True, done=11.0)
                for i in range(replies)}
    return RunData(reqs, outcomes, 0.0, ({}, {}), trace, (10.5, 40.5),
                   {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
                   {"silo": d}, 1)


def test_sharded_solve_device_ms_is_per_chip_per_solve():
    read = spec.metric_reader("linalg.sharded_solve_device_ms").read
    # 150 solves, 0.3 s on each of 4 chips: 2 ms of device time a solve,
    # however many replies the host clock counts.
    assert read(_traced_run(0.3)) == pytest.approx(2.0)
    assert read(_traced_run(0.3, planes=1)) == pytest.approx(2.0)
    assert read(_traced_run(0.3, solves=0)) is None
    assert read(_traced_run(0.0)) is None


def test_sharded_tri_solve_roofline_on_four_planes():
    from bench import roofline

    read = spec.metric_reader("sharded_tri_solve_roofline").read
    _, nbytes = roofline.sharded_tri_solve(16384, 4)
    # A chip streaming its share of the bytes at peak: 100%.
    fastest = nbytes / 4 / 819e9 * 150
    assert read(_traced_run(fastest)) == pytest.approx(100.0)
    # Each chip reading its whole (d/2)^2 tile twice a pass, as the block
    # layout's masked matvecs do: about a quarter of the roofline.
    slow = 2 * 2 * 4 * 8192 ** 2 / 819e9 * 150
    assert 24.0 < read(_traced_run(slow)) < 26.0
    assert read(_traced_run(0.0)) is None
    mixed = _traced_run(slow)
    mixed.dims = {"silo": 16384, "other": 8192}
    assert read(mixed) is None
    untraced = _traced_run(slow)
    untraced.trace = None
    assert read(untraced) is None


def test_solve_p95_reader_is_the_end_to_end_tail():
    from bench.run import end_to_end

    read = spec.metric_reader("loadgen.solve_p95_ms").read
    # 40 reads due 0.1 s apart, answered 1..40 ms late; one delta 500 ms
    # late that the read tail leaves out; one read never answered.
    reqs = [SimpleNamespace(kind="solve", idx=i, due=0.1 * i)
            for i in range(41)]
    reqs.append(SimpleNamespace(kind="delta", idx=41, due=0.05))
    outcomes = {i: SimpleNamespace(done=10.0 + 0.1 * i + 1e-3 * (i + 1))
                for i in range(40)}
    outcomes[41] = SimpleNamespace(done=10.55)
    run = RunData(reqs, outcomes, 10.0, ({}, {}), None, None, None, {}, 8)
    value = read(run)
    assert value == pytest.approx(38.05)
    assert value == pytest.approx(
        end_to_end(reqs, outcomes, 10.0)["solve_p95_ms"])
    run.outcomes = {41: outcomes[41]}
    assert read(run) is None
