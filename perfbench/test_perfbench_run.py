"""A whole run at smoke size on the CPU, with the chip check skipped: the
result line, and a check that catches a served token altered where it is
produced and a decode step that leaves the cache unchanged."""
import dataclasses
import json

import pytest

from perfbench import harness, run, tiny

ARGS = ["--workload", "tiny", "--seed", "4", "--seconds", "2", "--trace", "0"]


@pytest.fixture(autouse=True)
def _tiny_model(monkeypatch):
    monkeypatch.setattr(harness, "arch_config", tiny.arch_config)


def _result(capsys, **kw):
    rc = run.main(ARGS, cell=tiny.cell(), require_chip=False, **kw)
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(line)


def test_no_chip_no_result(capsys):
    assert run.main(ARGS, cell=tiny.cell()) == 1
    assert capsys.readouterr().out == ""


def test_sound_run_is_correct(capsys):
    res = _result(capsys)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"] and list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in
                                   tiny.cell().bench["end_to_end"]}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert set(res["checks"]) == set(tiny.SPEC["check"]["limits"])


def _alter_tokens(engine):
    step = engine.step

    def altered():
        return [dataclasses.replace(ev, token=(ev.token + 1) % 512)
                if ev.index == 3 else ev for ev in step()]
    engine.step = altered


def _keep_state(engine):
    decode = engine._decode_chunk

    def unchanged(params, caches, *a, **kw):
        return (caches,) + tuple(decode(params, caches, *a, **kw)[1:])
    engine._decode_chunk = unchanged


@pytest.mark.parametrize("fault", [_alter_tokens, _keep_state],
                         ids=["token_altered", "state_unchanged"])
def test_faults_are_not_correct(capsys, fault):
    res = _result(capsys, tamper=fault)
    assert res["correct"] is False
