"""``resume_dev_ms.reuse`` (PR 28): one metric over the program that resumes a
prefix hit's question, whatever that program is called on the side being
measured: ``jit_verify_step_batched`` before PR 28, ``jit_resume_chunk``
since. A wave step, a prefill or a gather is no sample of it."""

import pytest

import accepted
import readers
import trace_reduce

NAME = "resume_dev_ms.reuse"
OTHERS = {
    "jit_verify_step_ragged": [2.4, 100],
    "jit_prefill": [0.9, 3],
    "jit__gather_blocks_pallas": [0.2, 40],
    "jit_resume_chunk_helper_that_is_not_one": [0.0, 0],
}


def view(modules):
    trace = {"modules": modules, "ops": {}, "work": {}, "busy_s": 1.0, "window_s": 8.0}
    return readers.Run([], {}, trace, {})


@pytest.mark.parametrize(
    "program, seconds, events, want_ms",
    [
        ("jit_verify_step_batched", 3.0, 6, 500.0),  # the parent's side
        ("jit_resume_chunk", 0.15, 6, 25.0),  # this tree's
    ],
)
def test_reader_finds_a_sample_under_either_program_name(program, seconds, events, want_ms):
    modules = {k: v for k, v in OTHERS.items() if v[1]}
    modules[program] = [seconds, events]
    assert readers.read_layer_metric(NAME, view(modules)) == pytest.approx(want_ms)
    # The cleaned names of the device trace's module events are what it reads.
    assert trace_reduce.clean_name(f"{program}(1234)") == program


def test_no_resume_in_the_trace_is_no_sample_and_no_trace_is_none():
    modules = {k: v for k, v in OTHERS.items() if v[1]}
    assert readers.read_layer_metric(NAME, view(modules)) is None
    assert readers.read_layer_metric(NAME, readers.Run([], {}, None, {})) is None


def test_entry_agrees_with_its_file_and_lists_the_two_dense_reuse_cells():
    """By name: where the entry stands in the list and which cells joined
    the two it was brought for (PR 28) is not this test's to pin."""
    spec, entry = accepted.agreed(NAME)
    assert {"mistral7b-prefix-reuse", "deepseek7b-prefix-reuse"} <= set(entry["workloads"])
    assert entry["moves"] == "tokens_per_s" and entry["layer"] == "Jitted model steps"
    # The patterns of the wave and prefill metrics do not take the resume's time.
    for other in ("wave_step_dev_ms.reuse", "prefill_dev_ms_per_ktok.chat"):
        pattern = readers.load_layer_metric(other)["reader"]["pattern"]
        assert trace_reduce.matching({"jit_resume_chunk": [1.0, 1]}, pattern) == (0.0, 0)
