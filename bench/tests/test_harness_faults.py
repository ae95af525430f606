"""The timed path broken underneath a CPU-sized run: ``correct`` must
come out false. Each case plants one fault the served cells can have."""
from __future__ import annotations

import numpy as np
import pytest

import tinycell


def _alter_tokens(monkeypatch):
    """A token altered where it is produced: every decode step's tokens
    are shifted by one before the scheduler records them."""
    from repro.serve.scheduler import Scheduler
    orig = Scheduler.complete_step

    def complete_step(self, next_tokens, logits=None):
        return orig(self, (np.asarray(next_tokens) + 1) % 256, logits)
    monkeypatch.setattr(Scheduler, "complete_step", complete_step)


def _state_unchanged(monkeypatch):
    """A step that returns its state unchanged: the decode step never
    appends the new token's K/V to the page pools."""
    from repro.serve import paging
    monkeypatch.setattr(paging, "append_token_paged",
                        lambda k, v, kg, *a, **kw: (k, v, kg))


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_broken_path_is_not_correct(tmp_path, monkeypatch, fault):
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "")
    fault(monkeypatch)
    root = tinycell.make_root(tmp_path)
    rc, res, err = tinycell.run_tiny(root, 4242)
    assert rc == 0, err
    assert res["correct"] is False, err
    assert res["checks"]["served_gap_mean"]["value"] > \
        res["checks"]["served_gap_mean"]["limit"]
