"""The whole harness on the CPU at test size, past its look for a chip:
each tiny cell (a real cell cut to test size) comes out correct; the
lower-precision control in the program's place, and each fault the cell
can have planted under the timed path, come out not correct; and a
configuration, a traffic mix and a per-layer metric added as new files
plus BENCHMARK.json entries are found by name with no file edited."""

import functools
import hashlib
import json

import jax
import jax.numpy as jnp
import pytest

from bench import run as R
from bench.peaks import PEAKS
from bench.tests import tiny

SEED = 2 ** 31 + 77  # more than 32 signed bits hold
SECONDS = 0.5


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def _cache_off():
    was = jax.config.jax_enable_compilation_cache
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _run(root, cell, trace=False):
    c = R.load_cell(root, cell)
    if len(jax.devices()) < c.chips:
        pytest.skip(f"{cell} needs {c.chips} devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    return R.run(c, SEED, SECONDS, trace, PEAKS["TPU v5 lite"], trace_platform="cpu")


@pytest.mark.parametrize("cell", list(tiny.CELLS))
def test_cell_is_correct(root, cell):
    res = _run(root, cell, trace=True)
    assert res["correct"], res["compared"]
    assert list(res)[-1] == "compared"
    names = {m[0] for m in R.load_cell(root, cell).per_layer}
    assert set(res["metrics"]) <= names and res["metrics"]
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0


@pytest.mark.parametrize("cell", list(tiny.CELLS))
def test_end_to_end_metrics(root, cell):
    res = _run(root, cell)
    want = {m[0] for m in R.load_cell(root, cell).end_to_end}
    assert set(res["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("cell", list(tiny.CELLS))
def test_control_fails(root, cell, monkeypatch):
    """The float32 reference computed in float8 in the program's place."""
    loop = __import__(f"bench.loops.{tiny.TRAFFIC[tiny.CELLS[cell][1]]['loop']}",
                      fromlist=["Loop"]).Loop
    monkeypatch.setattr(loop, "check", functools.partialmethod(loop.check, quant="fp8"))
    res = _run(root, cell)
    assert not res["correct"], res["compared"]


# ----------------------------------------------------------------- faults
def _serve_fault(kind, monkeypatch):
    from repro.models import model as M

    real = M.decode_step

    def broken(params, cache, batch, position, cfg, unroll=False):
        logits, new = real(params, cache, batch, position, cfg, unroll)
        if kind == "token_altered":
            return logits.at[:, 7].add(100.0), new
        return logits, cache  # state unchanged

    monkeypatch.setattr(M, "decode_step", broken)


def _train_fault(kind, monkeypatch):
    from repro.train import train_step as TS

    real = TS.make_train_step

    def make(cfg, opt, settings):
        step = real(cfg, opt, settings)

        def broken(params, opt_state, batch):
            if kind == "half_batch":
                half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
                return step(params, opt_state, half)
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics  # state unchanged
        return broken

    monkeypatch.setattr(TS, "make_train_step", make)


FAULTS = [("tiny-serve-decode", _serve_fault, "token_altered"),
          ("tiny-serve-decode", _serve_fault, "state_unchanged"),
          ("tiny-train", _train_fault, "state_unchanged"),
          ("tiny-train", _train_fault, "half_batch")]


@pytest.mark.parametrize("cell,plant,kind", FAULTS,
                         ids=[f"{c}-{k}" for c, _, k in FAULTS])
def test_fault_is_caught(root, cell, plant, kind, monkeypatch):
    plant(kind, monkeypatch)
    res = _run(root, cell)
    assert not res["correct"], res["compared"]


# ------------------------------------------------------------ extensible
def _digest(path):
    return {p.relative_to(path).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(path.rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    def extra(root, bench):
        b = root / "bench"
        before.update(_digest(b))
        cfg = dict(tiny.CONFIGS["tiny-moe"], num_hidden_layers=1)
        (b / "configs" / "tiny-moe-1l.json").write_text(json.dumps(cfg))
        mix = dict(tiny.TRAFFIC["tiny-serve"], rate_per_s=20.0, slots=2)
        (b / "traffic" / "tiny-serve-2.json").write_text(json.dumps(mix))
        (b / "limits" / "tiny-new.json").write_text(json.dumps({"off_best_share": 0.1}))
        (b / "metrics" / "steps_per_s.new.py").write_text(
            "def read(m):\n    return m.window['steps'] / m.window['seconds']\n")
        bench["configs"].append({"name": "tiny-moe-1l", "source": "test",
                                 "file": "bench/configs/tiny-moe-1l.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": "tiny-new", "config": "tiny-moe-1l",
                                   "traffic": "tiny-serve-2", "chips": 1, "why": "test"})
        bench["per_layer"].append({"name": "steps_per_s.new", "unit": "1/s",
                                   "better": "higher", "source": "program_counter",
                                   "layer": "serving engine", "moves": "tokens_per_s",
                                   "workloads": ["tiny-new"]})

    before: dict = {}
    root = tiny.make_root(tmp_path, extra)
    after = _digest(root / "bench")
    assert all(after[k] == v for k, v in before.items())
    assert set(after) - set(before) == {
        "configs/tiny-moe-1l.json", "traffic/tiny-serve-2.json",
        "limits/tiny-new.json", "metrics/steps_per_s.new.py"}
    cell = R.load_cell(root, "tiny-new")
    assert cell.config["num_hidden_layers"] == 1 and cell.traffic["slots"] == 2
    res = R.run(cell, SEED, SECONDS, True, PEAKS["TPU v5 lite"], trace_platform="cpu")
    assert res["correct"]
    assert res["metrics"]["steps_per_s.new"]["value"] > 0


def test_every_seed_offers_the_same_work():
    """Every block of the open-loop schedule holds the same gaps and lengths
    in another order, the same for every seed; the seed draws the token
    ids. The supply never runs out."""
    from bench.loops.serve_open import Schedule

    t = dict(tiny.TRAFFIC["tiny-serve"], block=8)

    def block(seed, k):
        s = Schedule(t, seed, 256)
        reqs = [s[i] for i in range(8 * k, 8 * k + 8)]
        at = [0.0 if k == 0 else s[8 * k - 1][0]] + [r[0] for r in reqs]
        return ([round(b - a, 9) for a, b in zip(at, at[1:])],
                [len(r[1]) for r in reqs], [r[2] for r in reqs], reqs)

    a, b = block(1, 5), block(2 ** 40 + 3, 5)
    assert a[:3] == b[:3]
    assert a[:3] != block(1, 4)[:3] and sorted(a[1]) == sorted(block(1, 4)[1])
    assert any((x[1] != y[1]).any() for x, y in zip(a[3], b[3]))
