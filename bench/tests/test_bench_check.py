"""The consistency checker that decides ``correct``."""
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.check import Rec, reference_answers, verify  # noqa: E402

BASE = np.arange(0.0, 100.0, 2.0)          # 0, 2, ..., 98


def rng_answer(live, lo, hi):
    return reference_answers(np.sort(live), "range",
                             np.asarray([[lo], [hi]], float))


def history(stale=False, lose=False):
    """An insert of 5 acked at t=2; a range read submitted at t=3 (must see
    it); a range read answered at t=1.5 (must not); a find at t=4."""
    ins = Rec("insert", np.asarray([5.0]), submitted=1.0, done=2.0)
    live = np.append(BASE, 5.0)
    before = Rec("range", np.asarray([[4.0], [10.0]]), submitted=0.5,
                 done=1.5, answer=rng_answer(BASE, 4.0, 10.0))
    after = Rec("range", np.asarray([[4.0], [10.0]]), submitted=3.0,
                done=3.5, answer=rng_answer(BASE if stale else live,
                                            4.0, 10.0))
    q = np.asarray([5.0, 6.0, 7.0])
    find = Rec("find", q, submitted=4.0, done=4.5,
               answer=reference_answers(np.sort(BASE if lose else live),
                                        "find", q))
    return [before, ins, after, find]


def test_accepts_a_valid_interleaving():
    v = verify(BASE, history())
    assert v == {"wrong": 0, "unanswered": 0, "reads": 3, "inserts": 1}


def test_an_overlapping_read_may_see_either_state():
    ins = Rec("insert", np.asarray([5.0]), submitted=1.0, done=2.0)
    for live in (BASE, np.append(BASE, 5.0)):
        r = Rec("range", np.asarray([[4.0], [10.0]]), submitted=1.5,
                done=2.5, answer=rng_answer(live, 4.0, 10.0))
        assert verify(BASE, [ins, r])["wrong"] == 0


def test_rejects_a_stale_answer():
    assert verify(BASE, history(stale=True))["wrong"] == 1


def test_rejects_a_lost_insert():
    assert verify(BASE, history(lose=True))["wrong"] == 1


def test_rejects_an_answer_from_the_future():
    ins = Rec("insert", np.asarray([5.0]), submitted=2.0, done=2.5)
    r = Rec("range", np.asarray([[4.0], [10.0]]), submitted=0.5, done=1.0,
            answer=rng_answer(np.append(BASE, 5.0), 4.0, 10.0))
    assert verify(BASE, [r, ins])["wrong"] == 1


def test_counts_unanswered_and_checks_every_key():
    q = np.asarray([1.0, 2.0, 3.0])
    found, rank = reference_answers(BASE, "find", q)
    rank = rank.copy()
    rank[2] += 1
    recs = [Rec("find", q, 0.0, 1.0, (found, rank)),
            Rec("find", q, 0.0, None)]
    assert verify(BASE, recs) == {"wrong": 1, "unanswered": 1, "reads": 1,
                                  "inserts": 0}
