"""The offered work is the same in every run: only token ids follow --seed."""

import json
import os

import pytest

import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
MIXES = sorted({w["traffic"] for w in BENCH["workloads"]})
SEEDS = (7, 2**31 + 12345)


CELLS = {w["name"]: w for w in BENCH["workloads"]}


def _config_of(cell):
    (cfg,) = [c for c in BENCH["configs"] if c["name"] == CELLS[cell]["config"]]
    with open(os.path.join(REPO, cfg["file"])) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", MIXES)
def test_plan_is_a_function_of_the_file_alone(mix):
    a, b = traffic.build_plan(mix), traffic.build_plan(mix)
    assert a.requests == b.requests and len(a.requests) > 50


@pytest.mark.parametrize("mix", MIXES)
def test_seed_changes_token_ids_and_nothing_else(mix):
    plan = traffic.build_plan(mix)
    for req in plan.requests[:12]:
        one, two = (traffic.token_ids(req, s, 32768) for s in SEEDS)
        assert len(one) == len(two) == req.prompt_tokens
        assert one != two
        assert one == traffic.token_ids(req, SEEDS[0], 32768)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_request_fits_the_cache_and_the_model(cell):
    """By cell: a mix that several cells share is held to each one's cache."""
    plan, cfg = traffic.build_plan(CELLS[cell]["traffic"]), _config_of(cell)
    bt = cfg["serving"]["block_tokens"]
    for req in plan.requests:
        # What a hit installs is whole blocks; so are a prompt and an answer,
        # but where a page is longer than a question (a cache that keeps a
        # state a block, PR 41: the engine keeps the part-full last block).
        assert req.prefix_tokens % bt == 0
        if bt <= req.own_tokens:
            assert req.prompt_tokens % bt == 0 and req.answer_tokens % bt == 0
        total = req.prompt_tokens + req.answer_tokens
        assert total <= cfg.get("max_position_embeddings", total)
        assert -(-total // bt) <= cfg["serving"]["cache_blocks"]


@pytest.mark.parametrize("mix", [m for m in MIXES if traffic.load_params(m)["loop"] == "closed"])
def test_closed_lists_fix_which_asks_hit(mix):
    plan = traffic.build_plan(mix)
    params = plan.params
    owner = {}
    for c in range(plan.clients):
        asked, last = {}, None
        for req in plan.client_list(c):
            # One client owns a document, asks it in order, never twice in a row:
            # a hit always follows its own save by at least one other request.
            assert owner.setdefault(req.doc, c) == c
            assert req.ask == asked.get(req.doc, 0)
            assert req.doc != last
            asked[req.doc] = req.ask + 1
            last = req.doc
            assert req.expect_hit == (req.ask > 0)
    share = sum(r.expect_hit for r in plan.requests) / len(plan.requests)
    asks = params["asks_per_document"]
    assert abs(share - (asks - 1) / asks) < 0.02
    # The mix of prefix lengths is exact in every client's documents: whole
    # copies of the multiset, then the first of one more, in the file's order
    # (16 documents of 4 : 2 : 1 are two copies and two more of the first).
    whole, left = divmod(params["documents_per_client"], sum(params["prefix_tokens"].values()))
    for c in range(plan.clients):
        docs = {r.doc: r.prefix_tokens for r in plan.client_list(c)}
        ahead = 0
        for length, count in params["prefix_tokens"].items():
            got = sum(1 for v in docs.values() if v == int(length))
            assert got == whole * count + min(count, max(0, left - ahead))
            ahead += count


@pytest.mark.parametrize("mix", [m for m in MIXES if traffic.load_params(m)["loop"] == "open"])
def test_open_schedule_is_committed(mix):
    plan = traffic.build_plan(mix)
    due = [r.due_s for r in plan.requests]
    assert due == sorted(due) and due[0] < 0 < due[-1]
    assert due[-1] >= BENCH["run_seconds"]  # the window never runs dry
    in_window = [r for r in plan.requests if 0 <= r.due_s < BENCH["run_seconds"]]
    assert abs(len(in_window) / BENCH["run_seconds"] - plan.params["rate_rps"]) < 0.25 * plan.params["rate_rps"]
    assert all(r.prefix_tokens == 0 and not r.expect_hit for r in plan.requests)
