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
