"""The ``correct`` check, driven through a whole run on the CPU at test
size with the chip look skipped: a sound run passes, the lower-precision
control reads past the limit, and each fault the served path can have
turns ``correct`` false."""
from __future__ import annotations

import dataclasses
import time

import pytest

import tiny
from benchlib import cell_run


def run(tmp_path, *, hook=None, control=False):
    cell = tiny.tiny_cell(tmp_path, "burst")
    return cell_run.run(cell, seed=2**33 + 11, seconds=tiny.CHECK_SECONDS,
                        control=control, t_start=time.perf_counter(),
                        engine_hook=hook)


def _patch_decode(engine, broken):
    orig = engine.model.paged_decode_step_routed

    def step(params, pool, table, toks, pos, *, window):
        return broken(orig, params, pool, table, toks, pos, window)
    engine.model = dataclasses.replace(engine.model,
                                       paged_decode_step_routed=step)


def state_unchanged(engine):
    """The decode step returns the KV pool it was given."""
    def broken(orig, params, pool, table, toks, pos, window):
        logits, _, ids = orig(params, pool, table, toks, pos, window=window)
        return logits, pool, ids
    _patch_decode(engine, broken)


def half_batch(engine):
    """The decode step leaves out the second half of its rows."""
    def broken(orig, params, pool, table, toks, pos, window):
        logits, new_pool, ids = orig(params, pool, table, toks, pos,
                                     window=window)
        h = logits.shape[0] // 2
        return logits.at[h:].set(0.0), new_pool, ids
    _patch_decode(engine, broken)


def test_sound_run_is_correct_and_control_is_not(tmp_path):
    """One run: the program's own comparison passes, and the control, put
    in its place, turns the run's ``correct`` false."""
    res = run(tmp_path, control=True)
    limit = res["checks"]["p99_logit_gap_clear"]["limit"]
    assert res["program"]["correct"] is True
    assert res["program"]["p99_logit_gap_clear"] < limit
    assert res["correct"] is False
    assert res["checks"]["p99_logit_gap_clear"]["value"] > limit
    assert res["checks"]["tokens_compared"]["value"] >= \
        res["checks"]["tokens_compared"]["limit"]
    assert list(res)[-1] == "checks"
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"ttft_p95_ms", "itl_p95_ms",
                                   "output_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_fault_turns_correct_false(tmp_path, monkeypatch, fault):
    hook = {"state_unchanged": state_unchanged,
            "half_batch": half_batch}.get(fault)
    if fault == "token_altered":
        import repro.serving.engine as E
        orig = E.sample

        def altered(logits, **kw):
            out = orig(logits, **kw)
            if out.shape[0] > 1:        # decode rows: every other slot
                out = out.at[::2].set((out[::2] + 1) % 500)
            return out
        monkeypatch.setattr(E, "sample", altered)
    res = run(tmp_path, hook=hook)
    assert res["correct"] is False, res["checks"]
