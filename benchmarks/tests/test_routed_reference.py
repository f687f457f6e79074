"""The logits check on a model whose layers choose (PR 33). A top-k router
is discontinuous: where two scores lie closer than bf16's roundoff the served
program and the float32 reference pick different experts, and the swapped
expert's output is another vector, so the plain comparison fails a program
that is right. The cure, on a toy of its own (``data/routed_toy.py``): the
reference follows the program's sets on the compared rows, holds each set to
its own scores (``CHOICE_SLACK``) and the logits to the unchanged 2.5% / 15%.
Then the pair's wiring through a whole run, the refusals, and the pure
comparison against what ``run.py``'s statements said before it."""

import argparse
import json
import os
import sys

import numpy as np
import pytest

import run
import traffic

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if DATA not in sys.path:
    sys.path.insert(0, DATA)

import head_choice  # noqa: E402
import routed_toy as toy  # noqa: E402
from test_rehearsal import CLOSED, toy_run  # noqa: E402

ROUNDS, TOKENS = 128, 256
SEEDS = [3, 4, 5]


@pytest.fixture(scope="module", params=SEEDS)
def sides(request):
    """One prompt through both sides: the bf16 side's logits and sets, the
    float32 side's own logits, sets and scores."""
    seed = request.param
    params = toy.init_params(toy.CONFIG, seed)
    tokens = np.random.default_rng([seed, 1]).integers(0, toy.CONFIG["vocab_size"], size=TOKENS).tolist()
    got, chosen = toy.program(params, toy.CONFIG, tokens, ROUNDS)
    ref, own, scores = toy.own(params, toy.CONFIG, tokens, ROUNDS)
    return argparse.Namespace(params=params, tokens=tokens, got=got, chosen=chosen, ref=ref, own=own, scores=scores)


def test_the_plain_comparison_fails_a_router_that_is_right(sides):
    differ = np.any(np.sort(sides.own, -1) != np.sort(sides.chosen, -1), axis=-1)  # [rounds, sites]
    print(f"rows whose sets differ from the float32 side's own: {differ.any(-1).mean():.1%} "
          f"(site 0 {differ[:, 0].mean():.1%}, site 1 {differ[:, 1].mean():.1%}) of {ROUNDS}")
    assert 0 < differ.any(-1).mean() < 0.2
    failed, read = run.compare_logits(sides.got, sides.ref)
    assert read["rms"] > run.LOGITS_RMS_TOL and read["worst"] > run.LOGITS_MAX_TOL and "max_gap" not in read
    assert failed == [f"logits off the float32 reference: rms {read['rms']:.4f} worst {read['worst']:.4f}"]
    # The rows whose sets agree are as close as bf16 is: the miss is the router's alone.
    same = ~differ.any(-1)
    _, read = run.compare_logits(sides.got[same], sides.ref[same])
    assert read["rms"] < run.LOGITS_RMS_TOL and read["worst"] < run.LOGITS_MAX_TOL


def test_following_the_programs_sets_passes_the_unchanged_limits(sides):
    ref, gaps = toy.logits_following(sides.params, toy.CONFIG, sides.tokens, ROUNDS, sides.chosen)
    assert gaps.shape == (ROUNDS, 2) and ref.shape == sides.got.shape
    failed, read = run.compare_logits(sides.got, ref, gaps)
    assert failed == []
    assert read["rms"] <= 0.012 and read["worst"] <= 0.10  # inside 2.5% / 15% with room
    assert (read["rms_limit"], read["worst_limit"], read["choice_slack"]) == (0.025, 0.15, run.CHOICE_SLACK)
    # Some set is not the reference's own (a gap above nought), none far from it.
    assert 0 < read["max_gap"] <= 0.03 < run.CHOICE_SLACK == 0.10
    assert float(gaps[read["gap_row"], read["gap_site"]]) == read["max_gap"]
    # Fed its own sets the reference is its plain self, every gap under nought.
    again, gaps = toy.logits_following(sides.params, toy.CONFIG, sides.tokens, ROUNDS, sides.own)
    assert np.array_equal(np.asarray(again), np.asarray(sides.ref)) and float(gaps.max()) < 0


@pytest.mark.parametrize("row,site", [(5, 1), (100, 0)])
def test_a_set_that_drops_its_best_expert_for_the_worst_fails_on_the_gap(sides, row, site):
    wrong = sides.chosen.copy()
    best, worst = sides.own[row, site, 0], int(np.argmin(sides.scores[row, site]))
    assert best in wrong[row, site] and worst not in wrong[row, site]
    wrong[row, site][wrong[row, site] == best] = worst
    ref, gaps = toy.logits_following(sides.params, toy.CONFIG, sides.tokens, ROUNDS, wrong)
    failed, read = run.compare_logits(sides.got, ref, gaps)
    assert (read["gap_row"], read["gap_site"]) == (row, site) and read["max_gap"] > 10 * run.CHOICE_SLACK
    assert failed[0] == (
        f"the program's choice at row {row} site {site} lies {read['max_gap']:.4f} of its scores' rms "
        f"off the float32 reference's own (limit 0.1)"
    )


@pytest.mark.parametrize("spoil,says", [
    (lambda c: c.__setitem__((7, 1, 2), c[7, 1, 0]), r"row 7 site 1 is \[.*\]: not 4 distinct ids of range\(32\)"),
    (lambda c: c.__setitem__((0, 0, 3), 32), r"row 0 site 0 is \[.*32\]: not 4 distinct ids of range\(32\)"),
    (lambda c: c.__setitem__((127, 1, 0), -1), r"row 127 site 1 is \[-1, .*\]: not 4 distinct ids"),
    (lambda c: c[:, :1], r"of shape \(128, 1, 4\), not whole numbers of shape \[128 rounds, 2 sites, k\]"),
    (lambda c: c.astype(np.float32), r"choices are float32 of shape"),
], ids=["repeated", "out-of-range", "negative", "a-site-short", "no-ids"])
def test_a_set_that_is_not_k_distinct_ids_in_range_raises(spoil, says):
    params = toy.init_params(toy.CONFIG, 3)
    tokens = list(range(TOKENS))
    chosen = toy.own(params, toy.CONFIG, tokens, ROUNDS)[1].copy()
    spoiled = spoil(chosen)
    with pytest.raises(ValueError, match=says):
        toy.logits_following(params, toy.CONFIG, tokens, ROUNDS, chosen if spoiled is None else spoiled)


@pytest.mark.parametrize("program,says", [
    # A program that reports its choices beside a reference that cannot follow them.
    ({"reference": "reference", "choices": "head_choice:choices"},
     r"program.choices is 'head_choice:choices' .* reference module 'reference' has no logits_following"),
    ({"reference": "routed_toy"}, r"program.choices is None .* reference module 'routed_toy' has a logits_following"),
], ids=["choices-alone", "following-alone"])
def test_one_of_the_pair_without_the_other_stops_before_the_server(monkeypatch, program, says):
    with pytest.raises(ValueError, match=says):
        run.reference_and_choices(program)
    monkeypatch.setattr(run, "start_server", lambda *a: pytest.fail("the server was started"))
    args = argparse.Namespace(workload="made-up", seed=1, seconds=1.0, trace=0)
    serving = {"block_tokens": 16, "kv_bytes_per_token": 65536, "store_block_kib": 32}
    with pytest.raises(ValueError, match=says):
        run.execute(
            args, {"name": "made-up", "chips": 1}, {"name": "made-up", "serving": serving, "program": program},
            traffic._closed_plan("made-up", CLOSED), {},
        )


def test_the_pair_together_resolves_to_both():
    reference, choices = run.reference_and_choices({"reference": "head_choice", "choices": "head_choice:choices"})
    assert reference is head_choice and choices is head_choice.choices


def old_statements(got, ref):
    """``against_reference`` as it stood before PR 33, on its two expects."""
    import jax.numpy as jnp

    scale = float(jnp.sqrt(jnp.mean(ref * ref)))
    rms = float(jnp.sqrt(jnp.mean((got - ref) ** 2))) / scale
    worst = float(jnp.max(jnp.abs(got - ref))) / scale
    said = []
    if not bool(jnp.all(jnp.isfinite(got))):
        said.append("non-finite logits")
    if not (rms <= 0.025 and worst <= 0.15):
        said.append(f"logits off the float32 reference: rms {rms:.4f} worst {worst:.4f}")
    return said, rms, worst


def accepted_files():
    """The configurations whose file names NO ``program.choices``, chosen by
    what the file holds: a configuration whose layers choose (every one with
    a router) names them, is no case, and trips nothing."""
    bench = run.load_json(os.path.join(run.REPO, "BENCHMARK.json"))
    paths = [os.path.join(run.REPO, c["file"]) for c in bench["configs"]]
    return [p for p in paths if "choices" not in run.load_json(p)["program"]]


def test_the_two_dense_files_are_among_the_cases():
    assert {"mistral-7b-v0.3.json", "deepseek-llm-7b.json"} <= set(map(os.path.basename, accepted_files()))


@pytest.mark.parametrize("rms,worst,passes", [
    (0.0085, 0.046, True), (0.026, 0.046, False), (0.0085, 0.16, False), (float("nan"), 0.046, False),
], ids=["as-the-chip-read", "rms-over", "worst-over", "not-finite"])
@pytest.mark.parametrize("path", accepted_files(), ids=os.path.basename)
def test_an_accepted_file_is_compared_as_it_was(path, rms, worst, passes):
    """Such a file names no ``program.choices``: its rows get the plain
    reference and the statements they got, to the digit, on logits made to
    read a recorded rms and worst."""
    config = run.load_json(path)
    reference, choices = run.reference_and_choices(config["program"])
    assert choices is None and callable(reference.logits) and not hasattr(reference, "logits_following")
    rng = np.random.default_rng(11)
    rows, vocab = run.DECODE_STEPS_CHECKED + 1, config["vocab_size"]
    ref = rng.standard_normal((rows, vocab)).astype(np.float32) * 0.57
    scale = float(np.sqrt(np.mean(ref * ref)))
    off = np.clip(rng.standard_normal((rows, vocab)), -3, 3).astype(np.float32)
    off[3, 17] = 0.0
    off *= (rms if rms == rms else 0.0085) * scale / np.sqrt(np.mean(off * off))
    off[3, 17] = worst * scale  # one logit off by the worst, the rest by the rms
    got = ref + off
    if rms != rms:
        got[8, 0] = np.nan
    failed, read = run.compare_logits(got, ref)
    said, old_rms, old_worst = old_statements(got, ref)
    assert failed == said and (failed == []) == passes
    assert set(read) == {"rms", "rms_limit", "worst", "worst_limit", "ref_rms"}
    if passes:
        assert (read["rms"], read["worst"]) == (old_rms, old_worst)
        assert read["rms"] == pytest.approx(rms, rel=0.02) and read["worst"] == pytest.approx(worst, rel=1e-3)


@pytest.mark.parametrize("at_fault", [False, True], ids=["sound", "reports-the-last"])
def test_a_whole_run_of_a_file_that_names_its_choices(monkeypatch, capfd, at_fault):
    """``Instruments.step_chunk`` asks the program for its choices after every
    call of the check phase and never in the window; ``against_reference``
    hands row 0's of each round to ``logits_following``; the line says what
    was compared. A program whose reported sets lie far off the reference's
    scores is not ``correct``, by the gap, with row and site."""
    monkeypatch.setattr(head_choice, "CALLS", [])
    monkeypatch.setattr(head_choice, "REPORT_THE_LAST", at_fault)
    at_check, real = [], run.CellRun.check

    async def check(self):
        at_check.append(len(head_choice.CALLS))
        await real(self)

    monkeypatch.setattr(run.CellRun, "check", check)
    line, res, _ = toy_run(
        CLOSED, 2**31 + 31 + at_fault, program={"reference": "head_choice", "choices": "head_choice:choices"}
    )
    assert at_check == [0] and len(head_choice.CALLS) >= 6 * run.DECODE_STEPS_CHECKED
    assert line["failed"] == 0 and line["attempted"] >= 8 and list(line)[-1] == "compared"
    compared = line["compared"]
    assert [c["label"] for c in compared] == [
        "prompt 48 miss", "prompt 48 partial hit", "prompt 80 miss", "prompt 80 partial hit",
    ]
    err = capfd.readouterr().err
    for c in compared:
        assert c["rms"] <= c["rms_limit"] == 0.025 and c["worst"] <= c["worst_limit"] == 0.15
        assert c["choice_slack"] == run.CHOICE_SLACK and c["gap_site"] == 0 and 0 <= c["gap_row"] <= 8
        assert f"logits {c['label']}: rms {c['rms']:.5f} worst {c['worst']:.5f}" in err
        assert f"widest choice gap {c['max_gap']:.5f} (slack 0.1)" in err
    if at_fault:
        assert not line["correct"] and all(c["max_gap"] > 1 for c in compared)
        assert err.count("of its scores' rms off the float32 reference's own (limit 0.1)") == 2 * 4
    else:
        assert line["correct"] and all(c["max_gap"] <= 0.05 for c in compared), compared
    json.dumps(line)  # the line is plain numbers and strings
