"""The comparison must tell a broken timed path from a sound one.

* The control: the reference computed one precision below the stated
  float32 (one bfloat16 pass) comes out as not correct; float32 as
  correct.  The same ``bench/control.py`` runs on the chip at the
  cells' own size.
* Faults planted under the timed path (``bench/faults.py``), in a run
  that skips the look for a chip and drives everything else
  (``--rehearse``): an answer altered where it is produced; half of
  each batch left out, with the other half's answers given in its
  place; and a window that returns the previous window's answers
  unchanged.  Each comes out as not correct.  The same faults are read
  on the chip at the cell's own size with ``bench/faults.py``.
  (One chip, so no exchange between chips to leave out.)

On the CPU (``JAX_PLATFORMS=cpu``); the kernels take the jnp route here
so that the builds stay short.
"""

import json

import pytest

import control
import faults
import run
from repro.plan.cache import PlanCache

ORIGINAL_FINALIZE = PlanCache.finalize

CONFIG = {"corpus": {"generator": "contrastive_surrogate", "d": 768,
                     "n_topics": 64, "subclusters": 16, "intrinsic": 96},
          "corpus_seed": 768, "k": 10, "recall_at_10_min": 0.88}


def test_control_fails_and_float32_passes():
    rows = {r["precision"]: r for r in control.readings(
        CONFIG, ["float32", "bf16"], n=3000, pool=256)}
    assert rows["float32"]["correct"]
    assert not rows["bf16"]["correct"]
    assert rows["bf16"]["score_gap"] > 30 * rows["float32"]["score_gap"]


def rehearse(capsys, workload="cohere768.batch-closed"):
    assert run.main(["--workload", workload, "--seed", str(2**31 + 5),
                     "--seconds", "1", "--rehearse"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture
def jnp_route(monkeypatch):
    monkeypatch.setenv("QUIVER_DISPATCH", "ref")


def test_sound_run_is_correct(jnp_route, capsys):
    out = rehearse(capsys)
    assert out["correct"] and out["failed"] == 0
    assert "metrics" not in out and "device" not in out


def test_answer_altered_is_not_correct(jnp_route, capsys):
    with faults.planted("altered"):
        out = rehearse(capsys)
    assert not out["correct"]
    assert out["checks"]["score_gap"]["value"] > 1e-3


def test_half_the_batch_left_out_is_not_correct(jnp_route, capsys):
    with faults.planted("half_batch"):
        out = rehearse(capsys)
    assert not out["correct"]
    assert out["checks"]["recall_at_10"]["value"] < 0.88


def test_stale_window_is_not_correct(jnp_route, capsys):
    with faults.planted("stale"):
        out = rehearse(capsys)
    assert not out["correct"]


def test_faults_tool_reports_each_fault(jnp_route, capsys):
    assert faults.main(["--workload", "cohere768.batch-closed",
                        "--seeds", str(2**31 + 6), "--faults",
                        "altered,stale", "--seconds", "1",
                        "--rehearse"]) == 0
    rows = [json.loads(x) for x in
            capsys.readouterr().out.strip().splitlines()]
    assert [r["fault"] for r in rows] == ["altered", "stale"]
    assert not any(r["correct"] for r in rows)
    assert PlanCache.finalize is ORIGINAL_FINALIZE


def test_no_tpu_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "cohere768.batch-closed", "--seed", "1",
                  "--seconds", "1"])
    assert e.value.code == 2
    assert capsys.readouterr().out == ""
