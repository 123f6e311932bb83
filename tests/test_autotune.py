"""Autotune bucket table: deterministic resolution, fallbacks, precedence.

The tuned-tile table is load-bearing for the hot path (every counting entry
resolves ``None`` knobs through it), so its failure modes are pinned here:
a missing or malformed ``tuned_configs.json`` must silently reproduce the
pre-autotune defaults, explicit caller integers must always win, and the
same (kind, L, N, B) must always land in the same bucket.
"""
import json

import pytest

from repro.kernels import autotune
from repro.kernels.autotune import DEFAULTS, TileConfig


@pytest.fixture(autouse=True)
def _fresh_table_cache():
    autotune.clear_cache()
    yield
    autotune.clear_cache()


# ---------------------------------------------------------------------------
# Bucket keys
# ---------------------------------------------------------------------------


def test_bucket_key_format_and_pow2_rounding():
    assert autotune.bucket_key("count", 3, 1024, 8) == "count:L3:N1024:B8"
    assert autotune.bucket_key("track", 5, 1000, 7) == "track:L5:N1024:B8"
    assert autotune.bucket_key("count", 2, 1025, 9) == "count:L2:N2048:B16"
    assert autotune.bucket_key("count", 1, 1, 1) == "count:L1:N1:B1"


def test_bucket_key_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown kernel kind"):
        autotune.bucket_key("fuse", 3, 128, 8)


def test_bucket_key_deterministic():
    keys = {autotune.bucket_key("count", 4, 4096, 32) for _ in range(50)}
    assert len(keys) == 1


# ---------------------------------------------------------------------------
# resolve(): table entry > defaults, explicit overrides > everything
# ---------------------------------------------------------------------------


def test_resolve_missing_table_falls_back_to_defaults(tmp_path):
    cfg = autotune.resolve("count", 3, 128, 8,
                           path=str(tmp_path / "absent.json"))
    assert cfg == DEFAULTS["count"]
    assert cfg == TileConfig(128, 128, 0, 32)


def test_resolve_malformed_table_falls_back_to_defaults(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text("{not json")
    assert autotune.resolve("track", 4, 256, 16, path=str(p)) \
        == DEFAULTS["track"]


def test_resolve_missing_bucket_falls_back_to_defaults(tmp_path):
    p = tmp_path / "t.json"
    p.write_text(json.dumps(
        {"configs": {"count:L9:N8:B8": {"block_next": 8}}}))
    assert autotune.resolve("count", 3, 128, 8, path=str(p)) \
        == DEFAULTS["count"]


def test_resolve_uses_tuned_entry_and_fills_missing_fields(tmp_path):
    p = tmp_path / "t.json"
    key = autotune.bucket_key("count", 3, 128, 8)
    p.write_text(json.dumps(
        {"configs": {key: {"block_next": 8, "block_prev": 16}}}))
    cfg = autotune.resolve("count", 3, 128, 8, path=str(p))
    assert (cfg.block_next, cfg.block_prev) == (8, 16)
    # fields absent from the entry come from DEFAULTS
    assert cfg.window_tiles == DEFAULTS["count"].window_tiles
    assert cfg.chunk == DEFAULTS["count"].chunk


def test_resolve_serves_a_table_only_on_its_backend(tmp_path):
    """CPU interpret-mode winners never reach the chip."""
    p = tmp_path / "t.json"
    key = autotune.bucket_key("count", 3, 1024, 8)
    p.write_text(json.dumps({"backend": "cpu", "configs": {key: {
        "block_next": 8, "block_prev": 16}}}))
    on_cpu = autotune.resolve("count", 3, 1024, 8, path=str(p),
                              backend="cpu")
    assert (on_cpu.block_next, on_cpu.block_prev) == (8, 16)
    on_tpu = autotune.resolve("count", 3, 1024, 8, path=str(p),
                              backend="tpu")
    assert on_tpu == DEFAULTS["count"]


def test_resolve_explicit_overrides_beat_tuned_entry(tmp_path):
    p = tmp_path / "t.json"
    key = autotune.bucket_key("count", 3, 128, 8)
    p.write_text(json.dumps({"configs": {key: {
        "block_next": 8, "block_prev": 8, "window_tiles": 2, "chunk": 16}}}))
    cfg = autotune.resolve("count", 3, 128, 8, block_prev=64, chunk=4,
                           path=str(p))
    assert cfg == TileConfig(block_next=8, block_prev=64,
                             window_tiles=2, chunk=4)


def test_resolve_deterministic_across_calls(tmp_path):
    p = tmp_path / "t.json"
    key = autotune.bucket_key("track", 4, 4096, 32)
    p.write_text(json.dumps({key: {"block_next": 32, "block_prev": 32}}))
    got = {autotune.resolve("track", 4, 4096, 32, path=str(p))
           for _ in range(20)}
    assert got == {TileConfig(32, 32, 0, 8)}


# ---------------------------------------------------------------------------
# Checked-in table (when present) is well-formed and bucket-key addressed
# ---------------------------------------------------------------------------


def test_checked_in_table_entries_are_valid_buckets():
    table = autotune.load_table()
    fields = {"block_next", "block_prev", "window_tiles", "chunk"}
    for key, entry in table.items():
        kind, lpart, npart, bpart = key.split(":")
        assert kind in DEFAULTS
        levels = int(lpart[1:])
        cap = int(npart[1:])
        batch = int(bpart[1:])
        assert autotune.bucket_key(kind, levels, cap, batch) == key
        assert set(entry) <= fields
        assert all(isinstance(v, int) and v >= 0 for v in entry.values())
        cfg = autotune.resolve(kind, levels, cap, batch)
        for f in fields:
            want = entry.get(f, getattr(DEFAULTS[kind], f))
            assert getattr(cfg, f) == want


# ---------------------------------------------------------------------------
# Cost model: sane, deterministic ranking
# ---------------------------------------------------------------------------


def test_candidate_configs_respect_cap_and_kind():
    count_cands = autotune.candidate_configs("count", 64, 32)
    assert all(c.block_next <= 64 and c.block_prev <= 64
               for c in count_cands)
    assert all(c.window_tiles == 0 for c in count_cands)
    assert {c.chunk for c in count_cands} == {8, 16, 32}
    track_cands = autotune.candidate_configs("track", 64, 32)
    assert {c.chunk for c in track_cands} == {DEFAULTS["track"].chunk}


def test_model_time_positive_and_deterministic():
    cfg = TileConfig(8, 8, 0, 8)
    t1 = autotune.model_time("count", 3, 1024, 8, cfg)
    t2 = autotune.model_time("count", 3, 1024, 8, cfg)
    assert t1 == t2 > 0.0


def test_rank_candidates_deterministic_shortlist():
    a = autotune.rank_candidates("count", 3, 1024, 8, top_k=4)
    b = autotune.rank_candidates("count", 3, 1024, 8, top_k=4)
    assert a == b
    assert 1 <= len(a) <= 4
    assert all(isinstance(c, TileConfig) for c in a)
