"""The whole run at test size on the CPU, with the timed path broken
underneath: each fault a cell can have must turn ``correct`` false, and the
unbroken run must stay correct."""
import numpy as np
import pytest

from bench.tests.tiny import run_cell, tiny_cell


def _alter_scores(engines, session):
    """The oracle's scoring step answers with a shifted label log-prob."""
    runner = engines["oracle"].runner
    orig = runner.logprobs

    def logprobs(tokens, last, extra=None):
        out = np.array(orig(tokens, last, extra))
        out[0, 259] += 1.0
        return out

    runner.logprobs = logprobs


def _flip_verdicts(engines, session):
    """The oracle's predicate answers true for its first row."""
    eng = engines["oracle"]
    orig = eng.predicate

    def predicate(prompts):
        passed, score = orig(prompts)
        passed = np.array(passed)
        passed[0] = not passed[0]
        return passed, score

    eng.predicate = predicate


def _alter_tokens(engines, session):
    """Decode serves slot 0 a token of its own choosing."""
    runner = engines["oracle"].runner
    orig = runner.decode

    def decode(tokens, lens):
        logits = np.array(orig(tokens, lens))
        logits[0, 65] = logits[0].max() + 50.0
        return logits

    runner.decode = decode


def _swap_labels(engines, session):
    """The oracle's scoring step reads the two label ids the wrong way round."""
    runner = engines["oracle"].runner
    orig = runner.logprobs

    def logprobs(tokens, last, extra=None):
        out = np.array(orig(tokens, last, extra))
        out[:, [259, 260]] = out[:, [260, 259]]
        return out

    runner.logprobs = logprobs


CASCADE = {"records_per_query": 64, "clients": 2}


def test_cascade_run_is_correct_and_returns_rows(capsys):
    res, err = run_cell(tiny_cell("cascade", **CASCADE), capsys, with_err=True)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0, \
        res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"rows_per_s", "query_p50_s", "setup_s"}
    returned = int(err.split("'returned_rows_compared': ")[1].split("}")[0])
    assert returned > 0     # the output holds rows, and they were compared


@pytest.mark.parametrize("fault, number", [
    (_alter_scores, "oracle_logprob_gap"),
    (_flip_verdicts, "filter_output_mismatches"),
    (_swap_labels, "oracle_logprob_gap"),
])
def test_cascade_fault_is_caught(capsys, fault, number):
    res = run_cell(tiny_cell("cascade", **CASCADE), capsys, fault=fault)
    assert res["correct"] is False
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]


def test_map_run_is_correct_and_altered_token_is_caught(capsys):
    cell = tiny_cell("gist", records_per_query=4, clients=2)
    res = run_cell(cell, capsys)
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["rows_per_s"]["value"] > 0
    res = run_cell(cell, capsys, fault=_alter_tokens)
    assert res["correct"] is False
    assert res["checks"]["served_token_gap"]["value"] > 1.0
