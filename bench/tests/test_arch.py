"""An architecture brought as files alone: a configuration that names
``"architecture": "moe"`` runs through the whole harness (engine, warm-up,
window, check) with the Mixtral-style expert layer of ``data/arch/moe.py``
and no file of the harness changed for it; and a configuration that names
an architecture with no file fails, saying where it looked."""
import json
import time
from pathlib import Path

import pytest

from bench import model
from bench.harness import run_cell

DATA = Path(__file__).resolve().parent / "data"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _run(config: Path, seed: int, control=False, fault=None) -> dict:
    cell = json.loads((DATA / "tiny.sessions.json").read_text())
    cell.update(name="tiny.sessions", config=config.stem, traffic="sessions",
                chips=1, per_layer=[])
    return run_cell(cell, seed=seed, seconds=6.0, trace=False, device=CPU,
                    control=control, t_process=time.monotonic(),
                    log=lambda m: None, config_path=config, fault=fault)


def _reverse_router(pw, dw):
    """A routing fault: the router's expert columns reversed in the
    program's weights, so that every token goes to other experts with
    their gates."""
    for w in (pw, dw):
        moe = w.params["moe"]
        w.params = dict(w.params,
                        moe=dict(moe, router=moe["router"][..., ::-1]))


def test_expert_architecture_from_files_is_correct_and_control_is_not(
        monkeypatch):
    monkeypatch.setattr(model, "ARCH_DIR", DATA / "arch")
    r = _run(DATA / "tiny_moe.json", seed=2**31 + 13)
    c = r["check"]
    assert r["correct"], c
    assert r["attempted"] > 0 and r["failed"] == 0
    assert c["checked_tokens"]["value"] > 100
    rc = _run(DATA / "tiny_moe.json", seed=2**31 + 13, control=True)
    assert not rc["correct"], rc["check"]
    assert rc["check"]["max_logit_gap"]["value"] > \
        rc["check"]["max_logit_gap"]["limit"]


def test_routing_fault_is_not_correct(monkeypatch):
    monkeypatch.setattr(model, "ARCH_DIR", DATA / "arch")
    r = _run(DATA / "tiny_moe.json", seed=2**31 + 17, fault=_reverse_router)
    assert not r["correct"], r["check"]
    assert r["check"]["max_logit_gap"]["value"] > \
        r["check"]["max_logit_gap"]["limit"]


def test_absent_architecture_names_the_path(tmp_path):
    raw = json.loads((DATA / "tiny.json").read_text())
    raw["architecture"] = "no_such_arch"
    path = tmp_path / "absent.json"
    path.write_text(json.dumps(raw))
    with pytest.raises(FileNotFoundError,
                       match=str(model.ARCH_DIR / "no_such_arch.py")):
        model.load_config("absent", path=path)
