"""Faults planted in the timed path, to show that ``correct`` catches them.

Each fault takes ``patch(obj, name, value)`` (pytest's
``monkeypatch.setattr`` in the tests, a plain ``setattr`` in
``bench/control.py --fault``) and breaks one guarantee of the server:

  state_unchanged      a delta's factor update returns the factor it was
                       given; a sharded tenant's fuse of an upload leaves
                       its statistics as they were
  answer_altered       the stacked sweep's first lane, and every sharded
                       solve, comes back 1e-3 off
  half_left_out        a sweep solves the first half of its lanes and hands
                       their answers to the rest
  delta_not_journaled  the journal drops DELTA frames, the pool still ACKs
  exchange_left_out    the sharded programs' psums between chips are left
                       out: each chip keeps its own partial sums
"""
from __future__ import annotations


def state_unchanged(patch) -> None:
    from repro.server.backends import DenseBackend
    from repro.server.distributed import ShardedBackend

    patch(DenseBackend, "update", lambda self, factor, vectors, sign: factor)
    patch(ShardedBackend, "update",
          lambda self, factor, vectors, sign: factor)
    patch(ShardedBackend, "fuse", lambda self, delta, sign=1.0: None)


def answer_altered(patch) -> None:
    from repro.server import batch
    from repro.server.distributed import ShardedBackend

    real = batch.solve_stacked

    def altered(entries):
        ws = real(entries)
        return [ws[0] * (1 + 1e-3)] + ws[1:]

    patch(batch, "solve_stacked", altered)
    real_sharded = ShardedBackend.solve
    patch(ShardedBackend, "solve",
          lambda self, factor: real_sharded(self, factor) * (1 + 1e-3))


def half_left_out(patch) -> None:
    from repro.server import batch

    real = batch.solve_stacked

    def half(entries):
        keep = max(1, len(entries) // 2)
        ws = real(entries[:keep])
        return ws + [ws[i % keep] for i in range(len(entries) - keep)]

    patch(batch, "solve_stacked", half)


def delta_not_journaled(patch) -> None:
    from repro.fed import wire
    from repro.server.durability import Journal

    real = Journal.append

    def append(self, tenant, raw):
        if raw[5] == wire.FT_DELTA:
            return self.size
        return real(self, tenant, raw)

    patch(Journal, "append", append)


def exchange_left_out(patch) -> None:
    from repro.server import distributed

    patch(distributed, "_psum", lambda x, axes: x)


FAULTS = {f.__name__: f for f in (state_unchanged, answer_altered,
                                  half_left_out, delta_not_journaled,
                                  exchange_left_out)}
