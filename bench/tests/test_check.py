"""The output check at a size a test run can hold, on the CPU: a whole run
of a cell (engine, warm-up, window, check) over a two-layer model with
the qwen2.5 layer. A sound run is correct; a run with the fp8 control in
the program's place is not; a run whose decode step alters a token is
not correct."""
import json
import time
from pathlib import Path

import pytest

from bench.harness import run_cell

DATA = Path(__file__).resolve().parent / "data"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _cell(traffic: str) -> dict:
    cell = json.loads((DATA / "tiny.sessions.json").read_text())
    cell.update(name=f"tiny.{traffic}", config="tiny", traffic=traffic,
                chips=1, per_layer=[])
    if traffic == "chat":
        cell.update(clients=4)
        cell["engine"]["max_len"] = 1024
    return cell


def _run(traffic: str, seed: int, fault=None, control=False) -> dict:
    return run_cell(_cell(traffic), seed=seed, seconds=6.0, trace=False,
                    device=CPU, control=control, t_process=time.monotonic(),
                    log=lambda m: None, config_path=DATA / "tiny.json",
                    fault=fault)


def _alter_tokens(pw, dw):
    """A token altered where it is produced: every 5th token a decode
    step hands out is off by one."""
    step = dw.step
    n = [0]

    def altered():
        out = []
        for rid, tok, fin in step():
            n[0] += 1
            out.append((rid, (tok + 1) % 500 if n[0] % 5 == 0 else tok, fin))
        return out

    dw.step = altered


@pytest.mark.parametrize("traffic", ["sessions", "chat", "cold"])
def test_sound_run_is_correct_and_control_is_not(traffic):
    r = _run(traffic, seed=2**31 + 11)
    c = r["check"]
    assert r["correct"], c
    assert r["attempted"] > 0 and r["failed"] == 0
    assert c["checked_tokens"]["value"] > 100
    assert c["max_logit_gap"]["value"] <= c["max_logit_gap"]["limit"]
    rc = _run(traffic, seed=2**31 + 11, control=True)
    assert not rc["correct"], rc["check"]
    assert rc["check"]["max_logit_gap"]["value"] > \
        rc["check"]["max_logit_gap"]["limit"]


@pytest.mark.parametrize("traffic", ["sessions", "chat", "cold"])
def test_altered_token_is_not_correct(traffic):
    r = _run(traffic, seed=7, fault=_alter_tokens)
    assert not r["correct"]
    assert r["check"]["max_logit_gap"]["value"] > \
        r["check"]["max_logit_gap"]["limit"]
