"""A whole run of each kind of cell, on a CPU at a tiny size, with the
look for a chip skipped: sound, it reads ``correct``; with the served
token or answer altered where the head produces it, ``correct`` comes
out false."""

import json

import jax.numpy as jnp
import pytest

from bench import peaks, run
from tinycells import CELLS, jax_config_restored, make_root


def _result(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def _altered(maker):
    """A head maker whose heads serve the next id after the one they
    rank first: a wrong token or answer, produced in the head."""
    from repro.serve.heads import _with_operands

    def make(*a, **k):
        head = maker(*a, **k)

        def with_operands(q, *ops):
            out = head.with_operands(q, *ops)
            return out._replace(ids=jnp.where(out.ids >= 0, out.ids + 1,
                                              out.ids))

        return _with_operands(with_operands, head.operands)

    return make


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(autouse=True)
def cpu_host(monkeypatch):
    """Trace readers need peaks; lend the CPU the chip's for the test."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    with jax_config_restored():
        yield


def _run(root, cell, trace=0):
    return run.main(["--workload", cell, "--seed", str(2 ** 31 + 99),
                     "--seconds", "1", "--trace", str(trace)],
                    require_chip=False, root=root)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(root, cell, capsys):
    assert _run(root, cell, trace=1 if cell.endswith("lss") else 0) == 0
    res = _result(capsys)
    assert res["correct"] is True, res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_altered_answer_is_not_correct(root, cell, capsys, monkeypatch):
    import repro.serve.engine as engine
    monkeypatch.setattr(engine, "make_full_head",
                        _altered(engine.make_full_head))
    monkeypatch.setattr(engine, "make_lss_head",
                        _altered(engine.make_lss_head))
    assert _run(root, cell) == 0
    res = _result(capsys)
    assert res["correct"] is False, res


@pytest.mark.parametrize("cell", ["tiny-lm.decode.full", "tiny-w2v.score.full"])
def test_times_are_the_benchmarks_own(root, cell, capsys, monkeypatch):
    """The program's own stamps (a token's time in its stream, a
    request's resolution time) enter no metric: with them all broken to
    0, a run reads positive, finite numbers."""
    from repro.serve.decode.sessions import TokenStream
    from repro.serve.runtime.future import RankFuture
    append, set_result = TokenStream.append, RankFuture.set_result

    def broken_append(self, token, t=None):
        append(self, token, t=0.0)

    def broken_set_result(self, result):
        set_result(self, result)
        self.t_done = 0.0

    monkeypatch.setattr(TokenStream, "append", broken_append)
    monkeypatch.setattr(RankFuture, "set_result", broken_set_result)
    assert _run(root, cell) == 0
    res = _result(capsys)
    assert res["correct"] is True, res
    assert res["metrics"]
    assert all(0 < m["value"] < 1e6 for m in res["metrics"].values()), res
