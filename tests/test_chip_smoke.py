"""chip_smoke.py's phases at tiny widths on the CPU (the script itself runs
them at the flagship's full width on the chip): the same functions, sized by
their ``Sizes`` argument; the TPU requirement is stepped over by the test with
monkeypatch, never by an option of the script."""

import dataclasses
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:  # chip_smoke.py is a repo-root module
    sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY_CONF = """
import runpy
make_config = runpy.run_path({full!r})["make_config"]
def get_config():
    return make_config(vocab=64, hidden=16, layers=2, seq_len=8, batch=8,
                       batches=3, seed=0)
"""


@pytest.fixture(scope="module")
def sizes(tmp_path_factory):
    conf = tmp_path_factory.mktemp("smoke_conf") / "tiny_conf.py"
    conf.write_text(TINY_CONF.format(full=chip_smoke.FULL.trainer_config))
    return dataclasses.replace(
        chip_smoke.FULL, vocab=64, dim=16, train_batch=8, seq_len=8,
        train_steps=3, gen_batch=4, beam=2, max_len=6, requests=2, slots=4,
        lookup_ids=64, trainer_config=str(conf), trainer_batches=3,
        expect_kernels=False)


@pytest.fixture(scope="module")
def state(tmp_path_factory):
    """What earlier phases hand to later ones, filled as the cases run."""
    return {"workdir": str(tmp_path_factory.mktemp("smoke_work"))}


def _phase_line(capsys, phase):
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    assert lines and lines[-1]["phase"] == phase and lines[-1]["ok"] is True
    return lines[-1]


PHASES = ["train", "trainer_cli", "generate", "serve", "sharded_train",
          "sharded_lookup"]


@pytest.mark.parametrize("phase", PHASES)
def test_phase_at_tiny_width(phase, sizes, state, capsys):
    import jax

    if phase == "train":
        state["trained"] = chip_smoke.phase_train(sizes, 0)
        line = _phase_line(capsys, phase)
        assert len(line["losses"]) == 3 and line["losses"][-1] < line["losses"][0]
        assert line["tpu_custom_calls"] == 0  # CPU: every gate is off
    elif phase == "trainer_cli":
        state["trainer"] = chip_smoke.phase_trainer_cli(sizes, state["workdir"])
        line = _phase_line(capsys, phase)
        assert line["batches"] == 3 and line["bad_steps"] == 0
    elif phase == "generate":
        out = chip_smoke.phase_generate(sizes, state["trained"])
        assert out["beam_tokens"].shape == (4, 2, 6)
        assert _phase_line(capsys, phase)["greedy_equals_beam1"] is True
    elif phase == "serve":
        chip_smoke.phase_serve(sizes, state["trained"], state["trainer"],
                               state["workdir"], 0)
        second = _phase_line(capsys, phase)["second_boot"]
        assert second["bucket_compile_events"] == 0
        assert second["generation_cache_misses"] == 0
        assert second["generation_cache_loads"] > 0
    elif phase == "sharded_train":
        chip_smoke.phase_sharded_train(sizes, 0, jax.devices())
        line = _phase_line(capsys, phase)
        assert line["all_reduces"] > 0 and len(line["losses"]) == 3
    else:
        chip_smoke.phase_sharded_lookup(sizes, 0, jax.devices())
        assert _phase_line(capsys, phase)["equals_plain_gather"] is True


def test_failed_check_raises():
    with pytest.raises(chip_smoke.SmokeFailure, match="loss did not fall"):
        chip_smoke.check(False, "train: loss did not fall")


def test_no_tpu_exits_nonzero_and_prints_no_result(capsys):
    """On the CPU the script stops before any phase and prints nothing on
    standard output — no result line a reader could mistake for a run."""
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None) and "no TPU found" in str(e.value.code)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("chips,fails", [(1, False), (4, False), (1, True)],
                         ids=["one_chip", "four_chips", "failing_phase"])
def test_last_line_shape_and_exit_code(chips, fails, monkeypatch, capsys):
    """The driver's contract: the last line is one JSON object with exactly
    ``ok`` and ``device`` (platform, kind, count as JAX reports them); a
    failing phase makes ``ok`` false and the exit code non-zero; ``--chips
    4`` runs the sharded phases and no other."""
    ran = []
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": chips}
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda n: device)

    def one_chip(sizes, seed, workdir):
        assert sizes is chip_smoke.FULL and os.path.isdir(workdir)
        ran.append("one")
        if fails:
            chip_smoke.check(False, "serve: second boot compiled")

    monkeypatch.setattr(chip_smoke, "run_one_chip", one_chip)
    monkeypatch.setattr(chip_smoke, "run_four_chips",
                        lambda *a: ran.append("four"))
    rc = chip_smoke.main(["--chips", str(chips)])
    out = capsys.readouterr().out.strip().splitlines()
    assert ran == (["four"] if chips == 4 else ["one"])
    assert rc == (1 if fails else 0)
    assert json.loads(out[-1]) == {"ok": not fails, "device": device}
    assert json.loads(out[-2])["phase"] == "compile_cache"
    if fails:
        assert json.loads(out[-3])["error"].startswith("SmokeFailure")
