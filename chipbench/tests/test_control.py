"""The controls at a size a test can hold: the lower precision has to fail
the comparison that the sound arithmetic passes, and a timed path broken
underneath has to make ``correct`` false."""

import numpy as np
import pytest

from chipbench.harness import check, loader, runner, serving, training

SEED = 2 ** 31 + 77


@pytest.fixture(scope="module")
def train_numbers():
    cell = loader.load("mistral-7b.train-4k", rehearse=True)
    batches = cell.generator.batches(cell.traffic, SEED,
                                     int(cell.config["vocab_size"]))
    first = [next(batches), next(batches)]
    hyper = training.hyper_of(cell)
    ref = check.reference_training(cell, SEED, first, hyper)
    low = check.reference_training(cell, SEED, first, hyper,
                                   lower=check.round_fp8)
    return cell, ref, low


def test_training_reference_against_itself_passes(train_numbers):
    cell, ref, _ = train_numbers
    assert all(c.ok and c.value == 0 for c in check.compare_training(
        ref, ref, cell.spec["limits"]))


def test_training_fp8_control_fails(train_numbers):
    cell, ref, low = train_numbers
    compared = check.compare_training(low, ref, cell.spec["limits"])
    assert not all(c.ok for c in compared)
    by = {c.name: c for c in compared}
    assert not by["gain_grad_diff_worst_leaf"].ok


def _run(cell_name, seconds=3):
    cell = loader.load(cell_name, rehearse=True)
    return runner.run_cell(cell, seed=SEED, seconds=seconds, trace=False,
                           rehearse=True, t_process=runner.now())


def _broken(monkeypatch, module, name, breaker):
    """The run as it is, with what ``module.name`` builds broken underneath
    by ``breaker`` (the timed path's engine or trainer)."""
    real = getattr(module, name)
    monkeypatch.setattr(module, name,
                        lambda *a, **kw: breaker(real(*a, **kw)))


def test_a_train_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    def frozen(eng):
        eng.step = lambda ids, labels: eng.eval_loss(ids, labels)  # no update
        return eng

    _broken(monkeypatch, training, "build", frozen)
    assert _run("mistral-7b.train-4k")["correct"] is False


def test_a_train_step_that_leaves_out_a_row_is_not_correct(monkeypatch):
    def half(eng):
        real = eng.step
        eng.step = lambda ids, labels: real(
            np.concatenate([np.asarray(ids)[:1]] * 2),
            np.concatenate([np.asarray(labels)[:1]] * 2))
        return eng

    _broken(monkeypatch, training, "build", half)
    assert _run("mistral-7b.train-4k")["correct"] is False


def _alter_third_token(greedy: bool):
    """An engine whose third token is altered where it is produced, in
    the requests of one kind only (greedy in effect, or sampled)."""
    def corrupt(engine):
        real = engine.step
        vocab = engine.model.config.vocab_size
        seen = set()

        def step():
            real()
            for req in list(getattr(engine, "_occupied", {}).values()):
                mine = (req.temperature < 1e-3) == greedy
                if mine and len(req.output) >= 3 and req.rid not in seen:
                    req.output[2] = (req.output[2] + 1 + req.rid) % vocab
                    seen.add(req.rid)

        engine.step = step
        return engine
    return corrupt


@pytest.mark.parametrize("greedy,number", [
    (True, "served_token_logit_gap"), (False, "sampled_token_nucleus_gap")])
def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch, capsys, greedy, number):
    _broken(monkeypatch, serving, "build_engine", _alter_third_token(greedy))
    line = _run("internlm2-1.8b.chat-batch", seconds=4)
    assert line["correct"] is False
    assert f"compared {number}" in capsys.readouterr().out.split(
        "NOT OK")[0].splitlines()[-1]


@pytest.mark.parametrize("fault,number", [
    ("no_cut", "sampled_token_nucleus_gap"),
    ("hot", "sampled_mass_above_off")])
def test_a_broken_sampler_is_not_correct(monkeypatch, capsys, fault, number):
    """The program's sampler with its top_p cut left out, or drawing at
    temperature 1.0 whatever the request says: only the sampled requests'
    numbers can see either."""
    import paddle_tpu.inference.serving as program

    real = program.sample_rows

    def broken(logits, keys, temps, top_ps, top_ks):
        if fault == "no_cut":
            top_ps = top_ps * 0 + 1.0
        else:
            temps = temps * 0 + 1.0
        return real(logits, keys, temps, top_ps, top_ks)

    monkeypatch.setattr(program, "sample_rows", broken)
    line = _run("internlm2-1.8b.chat-batch", seconds=4)
    out = capsys.readouterr().out
    assert line["correct"] is False
    bad = [l for l in out.splitlines() if "NOT OK" in l]
    assert any(number in l for l in bad)
    assert not any("served_token_logit_gap" in l for l in bad) \
        or fault == "hot"      # a hot sampler also breaks one-hot greedy rows


def test_the_sound_paths_are_correct():
    assert _run("mistral-7b.train-4k")["correct"] is True
    assert _run("internlm2-1.8b.chat-batch", seconds=4)["correct"] is True


@pytest.fixture(scope="module")
def control_rows(tmp_path_factory):
    """chipbench/control.py as it is run on the chip, at the rehearsal
    size: two sound seeds, the fp8 program, the rolled pool, one process."""
    import json

    from chipbench import control

    out = tmp_path_factory.mktemp("control") / "rows.jsonl"
    rc = control.main(["--workload", "internlm2-1.8b.chat-batch", "--seeds",
                       f"{SEED},{SEED + 1}", "--fp8-seeds", str(SEED + 2),
                       "--fault-seeds", str(SEED + 3), "--seconds", "4",
                       "--rehearse", "--out", str(out)])
    return rc, [json.loads(l) for l in out.read_text().splitlines()]


def test_controls_fail_and_sound_seeds_pass_in_one_process(control_rows):
    rc, rows = control_rows
    assert rc == 0
    assert [(r["kind"], r["passed"]) for r in rows] == [
        ("sound", True), ("sound", True), ("fp8_program", False),
        ("rolled_pool", False)]


def test_each_number_separates_its_fault_from_the_sound_runs(control_rows):
    _, rows = control_rows
    limits = loader.load("internlm2-1.8b.chat-batch",
                         rehearse=True).spec["limits"]
    for r in rows[:2]:
        for name, fault in (("served_token_logit_gap", "fp8_reference"),
                            ("sampled_token_nucleus_gap", "no_cut_reference"),
                            ("sampled_mass_above_off", "hot_reference")):
            assert r[name] <= limits[name] < r[fault][name], (name, r)
    # the pool with every page's content moved on by one: a wrong page
    assert rows[3]["served_token_logit_gap"] > \
        3 * limits["served_token_logit_gap"]


def test_round_fp8_is_float8_e4m3fn():
    import jax.numpy as jnp
    import ml_dtypes

    rng = np.random.default_rng(0)
    x = np.concatenate([rng.normal(0, 0.02, 50000), rng.normal(0, 1, 50000),
                        rng.normal(0, 100, 1000),
                        [0.0, 2 ** -9, 2 ** -10, 1.5 * 2 ** -9, 448.0,
                         0.0175, -0.0175]]).astype(np.float32)
    x = x[np.abs(x) <= 448]
    want = x.astype(ml_dtypes.float8_e4m3fn).astype(np.float32)
    got = np.asarray(check.round_fp8(jnp.asarray(x)))
    assert (got == want).all()
    assert (got != x).mean() > 0.9        # it does lower the precision
