"""The residual screen of ``bench/check.py`` on replies made by hand."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import check, reference


def test_screen_reads_alike_replies_once_and_alike():
    rng = np.random.default_rng(0)
    A, y = rng.normal(size=(64, 8)), rng.normal(size=64)
    base = reference.Ridge64(8)
    base.add(A, y)
    w1 = base.solve(0.1).astype(np.float32)
    w2 = base.solve(1.0).astype(np.float32)
    wrong = (w1 * (1 + 1e-3)).astype(np.float32)
    sigmas = [0.1, 1.0, 0.1, 0.1, 1.0, 1.0]
    results = [w1, w2, w1.copy(), wrong, w2.copy(), w1]
    qs = [SimpleNamespace(idx=i, sigma=s) for i, s in enumerate(sigmas)]
    outcomes = {i: SimpleNamespace(result=r, sent=0.0, done=1.0)
                for i, r in enumerate(results)}
    dep = SimpleNamespace(groups=[None])
    out = check.screen(dep, 0, 0, qs, outcomes, [], {}, None, base)
    for q in qs:
        alone = check.screen(dep, 0, 0, [q], outcomes, [], {}, None, base)
        assert alone[q.idx][0] == pytest.approx(out[q.idx][0], rel=1e-9)
        assert alone[q.idx][1] == out[q.idx][1] == 0
    assert out[0] == out[2] and out[1] == out[4]
    assert out[0][0] < 1e-6 and out[1][0] < 1e-6
    # the altered reply, and a right reply for another sigma, read far off
    assert out[3][0] > 100 * out[0][0] and out[5][0] > 100 * out[0][0]
