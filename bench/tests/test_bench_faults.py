"""A run of the harness, chip check skipped, with the timed path broken
underneath: ``correct`` has to come out false for each fault a cell can
have, and true with none.  Tiny key sets on the CPU (the jnp path)."""
import dataclasses
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, spec  # noqa: E402


# a YCSB E-like mix over the same keys: the checker's insert rules under the
# whole harness
MIXED = {"generator": "ops", "rate_rps": 60.0, "ops": [
    {"kind": "range", "share": 0.8, "keys": 1, "start": "zipfian",
     "theta": 0.99, "length": [1, 100]},
    {"kind": "insert", "share": 0.2, "keys": 1, "choose": "fresh_uniform"}],
    "warm": {"range": [128], "insert": 1}}


def tiny(name: str) -> spec.Cell:
    """The cell at 4,096 keys, its requests cut to 8 keys so that every
    batch stays in the warmed classes; ``mixed``: :data:`MIXED` over the
    first cell's configuration."""
    cell = spec.resolve("amzn-get64-r80" if name == "mixed" else name)
    cfg = dict(cell.config)
    cfg["dataset"] = dict(cfg["dataset"], n=4096)
    cfg["index"] = dict(cfg["index"], n_leaves=16)
    cfg["delta_fill"] = 64 if name == "mixed" else 0
    if name == "mixed":
        mix = MIXED
    else:
        mix = dict(cell.mix, rate_rps=60.0,
                   ops=[dict(o, keys=min(int(o["keys"]), 8))
                        for o in cell.mix["ops"]],
                   warm={"find": [128, 256]})
    return dataclasses.replace(cell, config=cfg, mix=mix)


def run(name: str, fault=None, control=None, seconds=0.4) -> dict:
    import jax

    return harness.run_cell(tiny(name), 12345, seconds, False,
                            jax.devices()[:1], time.monotonic(),
                            lambda m: None, fault=fault, control=control)


def _wrap(pack, attr, change):
    orig = getattr(pack, attr)

    def broken(qmat):
        a, b = orig(qmat)
        return change(a, b)

    setattr(pack, attr, broken)
    for width in (128, 256):        # the fault's own ops compile here, not
        broken(np.zeros((pack.n_tenants, width)))   # in the window


def half_the_batch(st):
    """Every other lane of each dispatch is never answered."""
    for attr in ("find", "find_range"):
        _wrap(st.fe.pack, attr,
              lambda a, b: (a.at[:, 1::2].set(a.dtype.type(0)),
                            b.at[:, 1::2].set(b.dtype.type(0))))


def answer_altered(st):
    """The first lane's rank comes back one too high."""
    for attr in ("find", "find_range"):
        _wrap(st.fe.pack, attr, lambda a, b: (a, b.at[:, 0].add(1)))


def state_unchanged(st):
    """Inserts are acknowledged and not applied."""
    st.index.backend.insert_batch = lambda keys: None


@pytest.mark.parametrize("name,fault", [
    ("amzn-get64-r80", None),
    ("amzn-get64-r80", half_the_batch),
    ("amzn-get64-r80", answer_altered),
    ("mixed", None),
    ("mixed", half_the_batch),
    ("mixed", answer_altered),
    ("mixed", state_unchanged),
])
def test_correct_catches_the_fault(name, fault):
    out = run(name, fault)
    assert out["correct"] is (fault is None), out["check"]
    assert out["attempted"] > 0
    assert list(out)[-1] == "check"


def test_the_f32_control_is_not_correct():
    """The control rounds keys and queries to f32: on keys that collide in
    f32, as 2x10^8 amzn keys do, it gives ranks the check refuses."""
    from bench import check
    from bench.control import f32_answers

    rng = np.random.default_rng(0)
    base = np.sort(2.0 ** 40 + rng.uniform(0, 2.0 ** 22, 4096))
    recs = []
    for i in range(32):
        q = base[rng.integers(0, base.size, 64)]
        recs.append(check.Rec("find", q, float(i), float(i) + 0.5,
                              check.reference_answers(base, "find", q)))
    assert check.verify(base, recs)["wrong"] == 0
    assert check.verify(base, f32_answers(base, recs))["wrong"] > 0


def test_a_compile_in_the_window_fails_the_run():
    """A batch in a capacity class the mix does not warm compiles in the
    window: the run is refused, not reported."""
    import jax

    cell = tiny("amzn-get64-r80")
    cell = dataclasses.replace(cell, mix=dict(cell.mix, warm={"find": []}))
    with pytest.raises(harness.CompiledInWindow):
        harness.run_cell(cell, 7, 0.3, False, jax.devices()[:1],
                         time.monotonic(), lambda m: None)
