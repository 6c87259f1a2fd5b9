"""The control — the plain reference computed one precision step lower
(float8 where the configuration stores bfloat16) and put in the program's
place — comes out not correct through a whole run's own check, at smoke
size."""
import dataclasses
import json

import numpy as np
import pytest

from perfbench import harness, run, tiny

SEED = 4
ARGS = ["--workload", "tiny", "--seed", str(SEED), "--seconds", "2",
        "--trace", "0"]


@pytest.fixture(autouse=True)
def _tiny_model(monkeypatch):
    monkeypatch.setattr(harness, "arch_config", tiny.arch_config)


def _control_in_place(engine):
    """Every token the engine serves is replaced by the one the control
    puts first after the request's prompt and the tokens served before."""
    ref = harness.load_module("references", tiny.CONFIG["reference"])
    prompts, tiers, served = {}, {}, {}
    submit, step = engine.submit, engine.step

    def submit_control(req):
        prompts[req.uid], tiers[req.uid] = np.asarray(req.prompt), req.tier
        served[req.uid] = []
        return submit(req)

    def step_control():
        out = []
        for ev in step():
            toks = np.concatenate([prompts[ev.uid],
                                   np.asarray(served[ev.uid], np.int32)])
            lg = ref.logits(tiny.CONFIG, SEED, [{
                "tokens": toks, "tier": tiers[ev.uid],
                "score_from": len(toks) - 1}], mode="control")
            tok = int(np.asarray(lg)[-1].argmax())
            served[ev.uid].append(tok)
            out.append(dataclasses.replace(ev, token=tok))
        return out

    engine.submit, engine.step = submit_control, step_control


def test_control_is_not_correct(capsys):
    assert run.main(ARGS, cell=tiny.cell(), require_chip=False,
                    tamper=_control_in_place) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is False
    assert res["checks"]["gap"]["value"] > res["checks"]["gap"]["limit"]


def test_reference_rows_and_packing():
    from perfbench.references import dense_gqa
    seqs = [{"tokens": np.arange(5, dtype=np.int32), "tier": "2/2",
             "score_from": 2},
            {"tokens": np.arange(3, dtype=np.int32), "tier": "8/8",
             "score_from": 0}]
    p = dense_gqa.pack(seqs, ["8/8", "2/2"])
    assert len(p["tokens"]) == dense_gqa.PAD_TO
    assert list(p["rows"]) == [2, 3, 4, 5, 6, 7]
    assert list(p["seg"][:9]) == [0] * 5 + [1] * 3 + [-1]
    assert list(p["tier"][:8]) == [1] * 5 + [0] * 3
    assert list(p["dec"][:8]) == [False] * 3 + [True] * 2 + [False, True,
                                                             True]
