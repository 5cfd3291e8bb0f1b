"""The needed-work count behind ``dispatch_roofline``."""
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import work  # noqa: E402


def leaves():
    # 4 leaves over keys 0..399, root pos = q, route_n = 400
    return work.ShardLeaves(a=1.0, b=0.0, route_n=400.0,
                            widths=np.asarray([10.0, 20.0, 30.0, 1e9]),
                            n_keys=400)


def test_counts_query_answer_and_window_keys():
    q = np.asarray([5.0, 150.0, 250.0, 399.0])
    got = work.needed_bytes(q, np.zeros(0), [leaves()], key_bytes=4)
    assert got == 4 * 8 + 4 * (10 + 20 + 30 + 400)
    got = work.needed_bytes(q, np.zeros(0), [leaves()], key_bytes=8)
    assert got == 4 * 12 + 8 * (10 + 20 + 30 + 400)


def test_count_is_the_same_across_tilings_and_padding():
    """The count depends on the live endpoints only: splitting them into
    query tiles of any size, with the pad lanes a tile carries dropped
    before counting, gives the same total."""
    rng = np.random.default_rng(0)
    q = rng.uniform(0, 400, 3000)
    want = work.needed_bytes(q, np.zeros(0), [leaves()], key_bytes=8)
    for tq in (128, 1000, 1024, 4096):
        tiles = [q[i:i + tq] for i in range(0, q.size, tq)]
        padded = [np.pad(t, (0, tq - t.size)) for t in tiles]
        live = [p[:t.size] for p, t in zip(padded, tiles, strict=True)]
        got = sum(work.needed_bytes(t, np.zeros(0), [leaves()],
                                    key_bytes=8) for t in live)
        assert got == want


def test_routes_to_shards_by_splits():
    two = [leaves(), work.ShardLeaves(1.0, -400.0, 400.0,
                                      np.full(4, 5.0), 400)]
    q = np.asarray([100.0, 400.0, 401.0, 700.0])
    got = work.needed_bytes(q, np.asarray([400.0]), [two[0], *two[1:]],
                            key_bytes=4)
    # 100 -> shard 0 leaf 1 (20); 400 -> shard 0 (a split goes left),
    # leaf 3 (capped at 400); 401 and 700 -> shard 1 (5 each)
    assert got == 4 * 8 + 4 * (20 + 400 + 5 + 5)
