"""Price-driven autotuner: decision determinism, cache robustness, escape
hatches, bytes-aware pricing — plus the multi-device end-to-end checks
(MoE "auto" bit-exactness on 8 devices, the 64-device scale smoke) run as
subprocesses with forced host devices."""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.core import costmodel
from repro.runtime import autotune as at

ROOT = pathlib.Path(__file__).resolve().parent.parent


# ------------------------------------------------------------ satellite 3
def test_seconds_backward_compatible():
    """No bytes: the original hops·t_w + t_s formula, unchanged."""
    assert costmodel.seconds(10) == pytest.approx(10 * 1.0e-6)
    assert costmodel.seconds(10, 2e-6, 5e-6) == pytest.approx(25e-6)


def test_seconds_scales_with_bytes():
    base = costmodel.seconds(10)
    # 50 GB moved per hop at 50 GB/s adds exactly 1 s per hop
    big = costmodel.seconds(10, bytes_per_hop=50e9, bandwidth=50e9)
    assert big == pytest.approx(base + 10.0)
    # monotone in message size
    a = costmodel.seconds(7, bytes_per_hop=1024)
    b = costmodel.seconds(7, bytes_per_hop=4096)
    assert b > a > costmodel.seconds(7)


# ------------------------------------------------------------- key space
def test_bucket_bytes_powers_of_two():
    assert at.bucket_bytes(0) == 64
    assert at.bucket_bytes(64) == 64
    assert at.bucket_bytes(65) == 128
    assert at.bucket_bytes(4096) == 4096
    assert at.bucket_bytes(5000) == 8192


def test_candidates_per_site():
    assert at.candidates("alltoall", "host") == ("loop", "fused", "sendrecv")
    assert "xla" in at.candidates("alltoall", "shard")
    assert "xla" in at.candidates("alltoall", "global")
    assert "sendrecv" in at.candidates("alltoall", "global")
    # the trace interpreter is a host-style replay: never a shard candidate
    assert "sendrecv" not in at.candidates("alltoall", "shard")
    assert "xla" not in at.candidates("matmul", "global")   # no fused-op form
    # emulated programs exclude xla: the fused op would mix idle devices
    assert "xla" not in at.candidates("alltoall", "shard", emulated=True)
    with pytest.raises(ValueError):
        at.candidates("alltoall", "bogus")


def test_analytic_prices_scale_with_bytes():
    lay = at.layout_for(4)
    small = at.analytic_prices("alltoall", lay, 64, ("loop", "fused"))
    large = at.analytic_prices("alltoall", lay, 1 << 20, ("loop", "fused"))
    assert all(large[s] > small[s] for s in small)


# ------------------------------------------- compute-keyed pipeline tuning
def test_candidates_overlap_fused_alltoall_shard_only():
    assert "overlap_fused" in at.candidates("alltoall", "shard")
    # still offered when the program is emulated (xla is not)
    assert "overlap_fused" in at.candidates("alltoall", "shard", emulated=True)
    assert "overlap_fused" not in at.candidates("allreduce", "shard")
    assert "overlap_fused" not in at.candidates("alltoall", "global")
    assert "overlap_fused" not in at.candidates("alltoall", "host")


def test_tunekey_compute_us_suffix_backward_compatible():
    """compute_us == 0 must format exactly as the pre-pipeline key so the
    existing schema-1 cache entries keep resolving."""
    k0 = at.TuneKey("alltoall", 2, 2, 1024, "float32", "shard")
    assert str(k0) == "alltoall|K2M2|b1024|float32|shard"
    k1 = at.TuneKey("alltoall", 2, 2, 1024, "float32", "shard", 512)
    assert str(k1) == "alltoall|K2M2|b1024|float32|shard|c512"
    assert k0 != k1
    k2 = at.TuneKey("alltoall", 2, 2, 1024, "float32", "shard", 512, True)
    assert str(k2) == "alltoall|K2M2|b1024|float32|shard|c512|emu"


def test_emulated_site_never_reuses_native_xla_decision(tmp_path):
    """Regression: ``emulated`` is part of the TuneKey. A native decision
    (possibly xla) memoized/cached for the same shapes must not be replayed
    at an emulated site, where the fused op would mix idle devices."""
    lay = at.layout_for(4)
    t = at.Autotuner(cache_path=tmp_path / "c.json", mode="analytic")
    d_native = t.decide("alltoall", lay, 256, site="shard")
    d_emu = t.decide("alltoall", lay, 256, site="shard", emulated=True)
    assert d_native.key != d_emu.key
    assert d_emu.key.emulated and str(d_emu.key).endswith("|emu")
    assert d_emu.strategy != "xla"
    assert "xla" not in d_emu.analytic_us
    # same shapes again: each variant replays its own memoized decision
    assert t.decide("alltoall", lay, 256, site="shard") is d_native
    assert t.decide("alltoall", lay, 256, site="shard", emulated=True) is d_emu


def test_bucket_compute_us():
    assert at.bucket_compute_us(0) == 0
    assert at.bucket_compute_us(1) == 1
    assert at.bucket_compute_us(2) == 2
    assert at.bucket_compute_us(3) == 4
    assert at.bucket_compute_us(300) == 512
    assert at.bucket_compute_us(25165) == 32768


def test_analytic_overlap_fused_discount():
    """With a large compute term the sequential strategies pay
    2·wire + compute while overlap_fused pays ~max(wire, compute): the
    overlap discount must rank it strictly cheaper."""
    lay = at.layout_for(8)
    pr = at.analytic_prices("alltoall", lay, 65536,
                            at.candidates("alltoall", "shard"),
                            compute_us=10_000)
    assert pr["overlap_fused"] < pr["loop"]
    assert pr["overlap_fused"] < pr["xla"]
    # without compute there is no round trip to hide: prices stay in the
    # plain-dispatch regime (overlap_fused ~ pipelined wire + group costs)
    pr0 = at.analytic_prices("alltoall", lay, 65536,
                             at.candidates("alltoall", "shard"))
    assert pr0["overlap_fused"] < pr["overlap_fused"]


def test_moe_compute_us_scales_with_ffn_flops():
    base = at.moe_compute_us(2, 32, 16, 64, 128)
    assert base > 0
    assert at.moe_compute_us(2, 32, 16, 64, 256) == pytest.approx(2 * base, abs=2)
    assert at.moe_compute_us(4, 32, 16, 64, 128) == pytest.approx(2 * base, abs=2)


def test_chunk_bytes_site_dependent():
    """Regression for the shard-site byte-bucketing fix: a global buffer
    (n, n, chunk) must key on the per-destination capacity chunk, not the
    n-times larger per-device buffer."""
    from repro.runtime.backends.auto import _chunk_bytes

    x_shard = np.zeros((8, 16, 4), np.float32)
    x_glob = np.zeros((8, 8, 16, 4), np.float32)
    assert _chunk_bytes(x_shard, "alltoall") == 16 * 4 * 4
    assert _chunk_bytes(x_glob, "alltoall", "global") == 16 * 4 * 4
    # non-alltoall kinds key on the full per-device vector
    assert _chunk_bytes(x_shard, "allreduce") == x_shard.size * 4


# -------------------------------------------------- satellite 4: determinism
def test_warm_cache_same_key_same_decision(tmp_path):
    lay = at.layout_for(4)
    t1 = at.Autotuner(cache_path=tmp_path / "cache.json")
    d1 = t1.decide("alltoall", lay, 256, site="host")
    assert d1.source == "measured" and d1.measured_us
    # a fresh tuner over the same cache returns the recorded decision
    t2 = at.Autotuner(cache_path=tmp_path / "cache.json")
    d2 = t2.decide("alltoall", lay, 256, site="host")
    assert d2.source == "cache"
    assert d2.strategy == d1.strategy
    # and within one tuner, repeat calls memoize (no decision-log growth)
    n = len(t2.decisions)
    d3 = t2.decide("alltoall", lay, 256, site="host")
    assert d3 is d2 and len(t2.decisions) == n


def test_corrupt_cache_falls_back_to_analytic(tmp_path):
    p = tmp_path / "cache.json"
    p.write_text("{this is not json")
    t = at.Autotuner(cache_path=p, mode="analytic")
    d = t.decide("alltoall", at.layout_for(4), 256, site="host")
    assert d.source == "analytic" and d.strategy in ("loop", "fused")


def test_missing_cache_falls_back_to_analytic(tmp_path):
    t = at.Autotuner(cache_path=tmp_path / "never_written.json", mode="analytic")
    d = t.decide("allreduce", at.layout_for(4), 256, site="host")
    assert d.source == "analytic"


def test_schema_mismatch_ignored(tmp_path):
    p = tmp_path / "cache.json"
    p.write_text(json.dumps({"schema": 999, "entries": {"x": {"strategy": "loop"}}}))
    t = at.Autotuner(cache_path=p, mode="analytic")
    assert t._cache == {}


def test_stale_cache_entry_with_unavailable_strategy_rederived(tmp_path):
    lay = at.layout_for(4)
    key = at.TuneKey("alltoall", lay.topo.K, lay.topo.M, 256, "float32", "host")
    p = tmp_path / "cache.json"
    p.write_text(json.dumps({
        "schema": at.SCHEMA_VERSION,
        "entries": {str(key): {"strategy": "xla"}},  # not a host candidate
    }))
    t = at.Autotuner(cache_path=p, mode="analytic")
    d = t.decide("alltoall", lay, 256, site="host")
    assert d.source == "analytic" and d.strategy != "xla"


def test_analytic_mode_writes_nothing(tmp_path):
    p = tmp_path / "cache.json"
    t = at.Autotuner(cache_path=p, mode="analytic")
    t.decide("alltoall", at.layout_for(4), 256, site="host")
    assert not p.exists()


def test_measure_writes_schema_versioned_cache(tmp_path):
    p = tmp_path / "cache.json"
    t = at.Autotuner(cache_path=p)
    d = t.decide("allreduce", at.layout_for(4), 256, site="host")
    assert d.source == "measured"
    raw = json.loads(p.read_text())
    assert raw["schema"] == at.SCHEMA_VERSION
    assert str(d.key) in raw["entries"]
    assert raw["entries"][str(d.key)]["strategy"] == d.strategy


# ------------------------------------------------------------ escape hatches
def test_forced_strategy_honored(tmp_path):
    t = at.Autotuner(cache_path=tmp_path / "c.json", force="fused")
    d = t.decide("alltoall", at.layout_for(4), 256, site="host")
    assert d.strategy == "fused" and d.source == "forced"
    assert not (tmp_path / "c.json").exists()  # forcing never measures


def test_forced_strategy_unavailable_degrades_to_candidate(tmp_path):
    # pallas_fused is not a host-site candidate: fall to a legal strategy
    t = at.Autotuner(cache_path=tmp_path / "c.json", force="pallas_fused")
    d = t.decide("alltoall", at.layout_for(4), 256, site="host")
    assert d.strategy in at.candidates("alltoall", "host")


def test_mode_off_returns_pre_autotuner_defaults(tmp_path):
    t = at.Autotuner(cache_path=tmp_path / "c.json", mode="off")
    assert t.decide("alltoall", at.layout_for(4), 256, site="shard").strategy == "xla"
    assert t.decide("alltoall", at.layout_for(4), 256, site="host").strategy == "loop"


def test_env_escape_hatches(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")
    assert at.Autotuner(cache_path=tmp_path / "c.json").mode == "off"
    monkeypatch.setenv("REPRO_AUTOTUNE", "analytic")
    assert at.Autotuner(cache_path=tmp_path / "c.json").mode == "analytic"
    monkeypatch.setenv("REPRO_AUTOTUNE", "overlap")
    assert at.Autotuner(cache_path=tmp_path / "c.json").force == "overlap"
    monkeypatch.setenv("REPRO_AUTOTUNE", "bogus")
    with pytest.raises(ValueError):
        at.Autotuner(cache_path=tmp_path / "c.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "elsewhere.json"))
    monkeypatch.delenv("REPRO_AUTOTUNE")
    assert at.Autotuner().cache_path == tmp_path / "elsewhere.json"


# ------------------------------------------------------- the auto backend
def test_auto_backend_matches_reference(tmp_path):
    """Whatever the tuner picks, the auto backend's result is bit-identical
    to the reference replay (single-device process: the availability guard
    degrades mesh-backed strategies to the fused global replay)."""
    from repro.dist import collectives as coll
    from repro.runtime.backends.auto import AutoBackend
    from repro.runtime.backends.reference import NumpyReferenceBackend

    tuner = at.Autotuner(cache_path=tmp_path / "c.json", mode="analytic")
    be = AutoBackend(tuner=tuner)
    ref = NumpyReferenceBackend()
    lay = at.layout_for(4)
    rng = np.random.default_rng(0)

    prog = coll.alltoall_program(lay)
    x = rng.integers(-8, 9, (4, 4, 8)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(be.run_alltoall(x, prog)), ref.run_alltoall(x, prog))

    par = coll.allreduce_program(lay)
    v = rng.integers(-8, 9, (4, 16)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(be.run_allreduce(v, par)), ref.run_allreduce(v, par))

    pb = coll.broadcast_program(lay, 1)
    np.testing.assert_array_equal(
        np.asarray(be.run_broadcast(v, pb)), ref.run_broadcast(v, pb))


def test_moe_site_report_shapes(tmp_path):
    from repro.configs import get_smoke_config
    from repro.dist.sharding import ShardRules

    cfg = get_smoke_config("mixtral-8x7b")
    rules = ShardRules(model_axis_size=4, data_axis_size=2)
    tuner = at.Autotuner(cache_path=tmp_path / "c.json", mode="analytic")
    rep = at.moe_site_report(cfg, rules, n_tokens=128, tuner=tuner)
    assert rep["status"] == "ok"
    assert rep["strategy"] in ("xla", "loop", "overlap", "overlap_fused")
    assert rep["moe_collectives"] in (
        "xla", "dragonfly", "dragonfly_overlap", "dragonfly_overlap_fused")
    assert rep["rounds"] >= 1 and rep["priced_hops"] > 0


# ------------------------------------------------------ combined guest site
def _tenant_embeddings():
    from repro.core.emulation import disjoint_embeddings
    from repro.core.topology import D3

    return tuple(disjoint_embeddings(D3(4, 2), [(1, 2), (1, 2)]))


def test_decide_combined_key_and_measured_win(tmp_path):
    """The combined site class: keyed on the guest-set signature, measured
    via reference replays of BOTH arms, and on disjoint same-shape guests
    the merged program wins (max vs sum of rounds)."""
    embs = _tenant_embeddings()
    tuner = at.Autotuner(cache_path=tmp_path / "c.json")
    dec = tuner.decide_combined("alltoall", embs, nbytes=4096)
    assert str(dec.key).endswith("|combined|emu|g2xD3(1,2)")
    assert dec.source == "measured"
    assert set(dec.measured_us) == {"combined", "time_mux"}
    assert dec.strategy == "combined"
    assert dec.analytic_us["combined"] < dec.analytic_us["time_mux"]
    # memoized, and placement-independent: the reversed tenant order is the
    # same signature, hence the same decision object
    assert tuner.decide_combined("alltoall", embs[::-1], nbytes=4096) is dec
    # a second tuner on the same cache path replays from disk
    warm = at.Autotuner(cache_path=tmp_path / "c.json")
    dec2 = warm.decide_combined("alltoall", embs, nbytes=4096)
    assert dec2.source == "cache" and dec2.strategy == dec.strategy


def test_decide_combined_modes_and_candidates(tmp_path):
    embs = _tenant_embeddings()
    assert at.candidates("alltoall", "combined") == ("combined", "time_mux")
    ana = at.Autotuner(cache_path=tmp_path / "a.json", mode="analytic")
    d = ana.decide_combined("alltoall", embs, nbytes=1 << 20)
    assert d.source == "analytic" and d.strategy == "combined"
    off = at.Autotuner(cache_path=tmp_path / "b.json", mode="off")
    assert off.decide_combined("alltoall", embs).strategy == "time_mux"
    forced = at.Autotuner(cache_path=tmp_path / "d.json", force="time_mux")
    assert forced.decide_combined("alltoall", embs).source == "forced"
    with pytest.raises(ValueError, match="at least one"):
        ana.decide_combined("alltoall", ())
    # plain keys are unchanged by the guests field (old caches stay valid)
    assert "|g" not in str(at.TuneKey("alltoall", 4, 2, 4096, "float32",
                                      "shard"))


# ------------------------------------------- subprocess end-to-end checks
@pytest.mark.slow
def test_moe_auto_bit_exact_8dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "moe_auto_check_script.py")],
        env=env, capture_output=True, text=True, timeout=1200,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "MOE AUTO CHECKS PASSED" in proc.stdout


@pytest.mark.slow
def test_scale_smoke_64dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=64"
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "scale_check_script.py")],
        env=env, capture_output=True, text=True, timeout=1800,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "ALL SCALE CHECKS PASSED" in proc.stdout
