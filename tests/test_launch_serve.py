"""The serving launcher: seeded weights without a trainer, and a non-zero
exit when a request or session fails for a reason other than a shed."""

from __future__ import annotations

import sys

import pytest

from repro.launch import serve
from repro.serve.runtime.future import (DeadlineExceededError,
                                        QueueFullError, RuntimeClosedError)
from repro.utils import compile_cache

ARGV = ["serve", "--arch", "qwen2-0.5b", "--reduced", "--train-steps", "0",
        "--head", "lss", "--mode", "decode", "--streams", "2",
        "--sessions", "2", "--steps", "2"]


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """Keep the launcher from pointing this test process's JAX at the
    checkout's compile cache."""
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: None)


def test_failures_exclude_explicit_sheds():
    excs = [None, QueueFullError("full"), DeadlineExceededError("late"),
            RuntimeError("boom"), RuntimeClosedError("closed")]
    assert serve._n_failed(excs) == 2


def test_decode_from_seeded_weights_exits_zero(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ARGV)
    serve.main()
    assert "2/2 sessions served" in capsys.readouterr().out


def test_exits_nonzero_when_a_session_fails(monkeypatch):
    monkeypatch.setattr(sys, "argv", ARGV)
    monkeypatch.setattr(serve, "serve_decode", lambda *a: 1)
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert exc.value.code not in (0, None)
