"""The one general traffic generator: a traffic file in, a plan out.

A traffic mix is a JSON file of parameters under ``benchmarks/traffic/``. It
fixes everything about the offered work except the token ids: who sends
what, of which length, when (open loop) or after what (closed loop), and
which requests share a prefix. All of that is drawn from the file's own
``schedule_seed``; the run's ``--seed`` only chooses token ids (hence every
store key) and the weights. So every run of a cell offers the same work.

Two loop kinds:

``closed``  K clients, each with a fixed list, each sending its next request
            when the last one completed. Documents (a prefix of one of the
            ``prefix_tokens`` lengths) are asked ``asks_per_document`` times,
            each ask a fresh question after the prefix. All asks of a
            document sit in ONE client's list, never back to back: a client
            is sequential, so ask n+1 is sent only after ask n's save
            returned, and which asks hit is fixed by the list, not by how
            fast the clients happen to run.
``open``    one schedule of due times (exponential gaps at ``rate_rps``) and
            unshared prompts; requests are sent when due, whatever the
            system is doing, and timed from when they were due.

Lengths come as multisets, ``{"2048": 4, "4096": 2, "8192": 1}``: the pool
of values is laid out in exactly that ratio and shuffled, not sampled, so
the mix is exact in every list.
"""

import dataclasses
import json
import os
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Request:
    """One request of a plan. ``doc`` and ``ask`` identify shared prefixes:
    requests with the same ``doc`` start with the same ``prefix_tokens``
    token ids; ``ask`` 0 is the one that has to compute and save them."""

    index: int  # position in the plan (unique)
    client: int  # closed loop: whose list; open loop: 0
    due_s: Optional[float]  # open loop: offset from the window's start
    doc: int
    ask: int
    prefix_tokens: int  # shared with the document's other asks (0: nothing shared)
    own_tokens: int  # this request's own prompt tokens after the prefix
    answer_tokens: int

    @property
    def prompt_tokens(self) -> int:
        return self.prefix_tokens + self.own_tokens

    @property
    def expect_hit(self) -> bool:
        return self.prefix_tokens > 0 and self.ask > 0


@dataclasses.dataclass(frozen=True)
class Plan:
    name: str
    loop: str  # "closed" | "open"
    clients: int  # closed loop: client count; open loop: cap on live requests
    requests: List[Request]
    params: Dict

    def client_list(self, client: int) -> List[Request]:
        return [r for r in self.requests if r.client == client]


def load_params(name: str) -> Dict:
    path = os.path.join(HERE, "traffic", f"{name}.json")
    with open(path) as f:
        return json.load(f)


def _exact_mix(multiset: Dict[str, int], n: int, rng) -> List[int]:
    """``n`` values in the multiset's ratio (whole copies of the multiset,
    then a prefix of one more), shuffled."""
    unit = [int(v) for v, count in multiset.items() for _ in range(int(count))]
    if not unit:
        raise ValueError("an empty multiset of lengths")
    values = (unit * (n // len(unit) + 1))[:n]
    return [int(v) for v in rng.permutation(values)]


def _closed_plan(name: str, p: Dict) -> Plan:
    rng = np.random.default_rng([int(p["schedule_seed"]), 1])
    clients, asks = int(p["clients"]), int(p["asks_per_document"])
    per_client = int(p["documents_per_client"])
    active = int(p.get("open_documents", 3))
    requests: List[Request] = []
    for c in range(clients):
        prefixes = _exact_mix(p["prefix_tokens"], per_client, rng)
        waiting = [(c * per_client + i, prefixes[i]) for i in range(per_client)]
        open_docs: List[list] = []  # [doc, prefix, next ask]
        last = None
        while waiting or open_docs:
            while waiting and len(open_docs) < active:
                doc, prefix = waiting.pop(0)
                open_docs.append([doc, prefix, 0])
            choices = [d for d in open_docs if d[0] != last]
            if not choices:
                # Only the document just asked is left: its remaining asks
                # would follow their own save at once. Leave them out.
                break
            d = choices[int(rng.integers(len(choices)))]
            requests.append(Request(
                index=len(requests), client=c, due_s=None, doc=d[0], ask=d[2],
                prefix_tokens=d[1], own_tokens=int(p["question_tokens"]),
                answer_tokens=int(p["answer_tokens"]),
            ))
            last = d[0]
            d[2] += 1
            if d[2] == asks:
                open_docs.remove(d)
    return Plan(name, "closed", clients, requests, p)


def _open_plan(name: str, p: Dict) -> Plan:
    rng = np.random.default_rng([int(p["schedule_seed"]), 2])
    rate, horizon = float(p["rate_rps"]), float(p["horizon_s"])
    lead_in = float(p.get("lead_in_s", 0.0))
    # Due times from -lead_in (the system is already under load when the
    # window opens) to the horizon. One draw, kept in the order drawn.
    n = int((horizon + lead_in) * rate * 1.5) + 16
    offsets = np.cumsum(rng.exponential(1.0 / rate, size=n)) - lead_in
    offsets = offsets[offsets < horizon]
    prompts = _exact_mix(p["prompt_tokens"], len(offsets), rng)
    answers = _exact_mix(p["answer_tokens"], len(offsets), rng)
    requests = [
        Request(
            index=i, client=0, due_s=float(offsets[i]), doc=i, ask=0,
            prefix_tokens=0, own_tokens=prompts[i], answer_tokens=answers[i],
        )
        for i in range(len(offsets))
    ]
    return Plan(name, "open", int(p["max_live"]), requests, p)


def build_plan(name: str) -> Plan:
    """The plan of traffic mix ``name``: a pure function of its file."""
    p = load_params(name)
    if p["loop"] == "closed":
        return _closed_plan(name, p)
    if p["loop"] == "open":
        return _open_plan(name, p)
    raise ValueError(f"traffic {name}: unknown loop kind {p['loop']!r}")


def token_ids(req: Request, seed: int, vocab: int) -> List[int]:
    """The prompt of ``req`` under ``--seed``: the document's prefix (the
    same ids for every ask of it) and then the request's own tokens."""
    out: List[int] = []
    if req.prefix_tokens:
        rng = np.random.default_rng([int(seed), 11, req.doc])
        out += rng.integers(0, vocab, size=req.prefix_tokens).tolist()
    rng = np.random.default_rng([int(seed), 12, req.index])
    out += rng.integers(0, vocab, size=req.own_tokens).tolist()
    return out


def store_bytes(plan: Plan, bytes_per_token: float) -> float:
    """What the store holds once every request of the plan has been served:
    each document's prefix once, each request's own tokens and answer, at
    ``bytes_per_token`` of the server's pool a token (what a block takes of
    it in whole allocation units, over the block's tokens)."""
    docs = {r.doc: r.prefix_tokens for r in plan.requests}
    tokens = sum(docs.values()) + sum(
        r.own_tokens + r.answer_tokens for r in plan.requests
    )
    return tokens * bytes_per_token
