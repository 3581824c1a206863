"""How `correct` is decided, shown to fail: at a size a test run can hold,
on the CPU. The chip's own readings, at the cells' own sizes, are in
PERF.md; the limits in `tiny/workloads/*.json` are this size's.

- the program, driven through the rest of a run, is correct;
- the control (the plain reference in float8, put in the program's place)
  is not;
- each fault a cell can have, planted underneath the timed path, is not.
"""
import json
import os

import numpy as np
import pytest

from benchmark.harness import check, serve, spec, train

from .conftest import TINY


def _spec(name, manifest):
    return spec.Spec(name, bench_dir=TINY, manifest=manifest)


@pytest.mark.parametrize("cell", ["tiny-train", "tiny-closed", "tiny-open"])
def test_program_is_correct(run_tiny, cell):
    line = run_tiny(cell)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "compared"
    for c in line["compared"].values():
        assert c["value"] <= c["limit"]
    assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_an_open_loop_counts_every_request_due_in_its_window(run_tiny):
    """The trace of `tiny-open` comes round every 0.4 s, so a window of
    0.8 s holds it twice wherever the seed starts it: the requests that
    fall due in the window's last step are sent and counted too."""
    line = run_tiny("tiny-open", seed=11, seconds=0.8)
    assert line["attempted"] == 16 and line["failed"] == 0
    assert line["correct"] is True, line["compared"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_train_control_in_float8_is_not_correct(tiny_manifest, seed):
    sp = _spec("tiny-train", tiny_manifest)
    ref = train.reference_readings(sp, seed)
    low = train.reference_readings(sp, seed, precision="fp8")
    numbers, _ = check.compare_train(low, ref)
    ok, compared = check.verdict(numbers, sp.cell["limits"])
    assert not ok, compared
    assert numbers["grad_norm_gap"] > sp.cell["limits"]["grad_norm_gap"]


def test_train_fault_half_batch_in_the_reference_is_not_correct(tiny_manifest):
    sp = _spec("tiny-train", tiny_manifest)
    ref = train.reference_readings(sp, 1)
    bad = train.reference_readings(sp, 1, fault="half_batch")
    numbers, _ = check.compare_train(bad, ref)
    assert numbers["grad_norm_gap"] > 10 * sp.cell["limits"]["grad_norm_gap"]


def test_fault_state_unchanged(run_tiny, monkeypatch):
    """A step that returns its state unchanged."""
    from paddle_tpu.optimizer import AdamW

    monkeypatch.setattr(AdamW, "_update_param", lambda self, p, g, lr: None)
    line = run_tiny("tiny-train")
    assert line["correct"] is False
    assert line["compared"]["grad_norm_gap"]["value"] == pytest.approx(1.0)
    assert line["compared"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_fault_half_of_the_batch_left_out(run_tiny, monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    from paddle_tpu.models import GPTForCausalLM

    whole = GPTForCausalLM.forward

    def half(self, input_ids, position_ids=None, labels=None):
        n = input_ids.shape[0] // 2
        return whole(self, input_ids[:n], position_ids,
                     None if labels is None else labels[:n])

    monkeypatch.setattr(GPTForCausalLM, "forward", half)
    line = run_tiny("tiny-train")
    assert line["correct"] is False
    c = line["compared"]["grad_norm_gap"]
    assert c["value"] > 10 * c["limit"]


@pytest.mark.parametrize("cell", ["tiny-closed", "tiny-open"])
def test_fault_token_altered_where_it_is_produced(run_tiny, monkeypatch, cell):
    """Every seventh token the engine appends is another one."""
    from paddle_tpu.serve import ServeEngine

    sound = ServeEngine._append_token
    count = [0]

    def altered(self, req, tok, now=None):
        count[0] += 1
        if count[0] % 7 == 0:
            tok = (int(tok) + 1) % 500 + 1
        return sound(self, req, tok, now=now)

    monkeypatch.setattr(ServeEngine, "_append_token", altered)
    line = run_tiny(cell)
    assert line["correct"] is False
    c = line["compared"]["served_logit_gap"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_control_in_float8_is_not_correct(tiny_manifest, seed):
    """The tokens that float8 puts first, at the positions of prompts and
    greedy continuations of this size, lie further below the reference's
    best than the limit allows."""
    sp = _spec("tiny-closed", tiny_manifest)
    rng = np.random.default_rng(seed)
    sample = [(rng.integers(1, 1024, 40), rng.integers(1, 1024, 60))
              for _ in range(4)]
    gap, n = serve.served_gap(sp, seed, sample, control="fp8")
    assert n == 240 and gap > sp.cell["limits"]["served_logit_gap"]
