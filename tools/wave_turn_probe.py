"""Where the host's turn between two decode waves goes, stage by stage.

A ``WaveDecoder`` on a bare harness (no store, no request path, tracing off)
runs ``--streams`` declared streams of ``--rounds`` one-token rounds each, as
``_generate`` drives them without a drafter: ``step_chunk``, then
``token_ids`` of what it resolved to, then the next round. One, two and three
streams ride the row buckets 1, 2 and 4. The model is a toy (``--layers``
layers of width ``--dim``) with a real vocabulary, 32,768 and 200,192, and
every stream stands ``--context`` tokens deep, so the wave's logits and its
page lists are as large as a cell's while its device step is short: what a
cycle has beside the step is the host's turn, and the turn is what is timed.

``perf_counter`` goes around the decoder's own stages of a flush, by wrapping
its methods on the instance (the decoder's code is what runs):

- ``sort``, ``assemble``: ``_sort`` and ``_assemble``;
- ``gate``: from ``_assemble``'s return to ``launch``'s entry, the exclusive
  acquisition of a gate nobody else wants;
- ``launch``: ``launch`` (pack, the jitted call's dispatch, the ids' copy
  started);
- ``release``: from ``launch``'s return to ``_resolve``'s entry;
- ``resolve``: ``_resolve`` (until PR 54 one logits slice an entry);
- ``hand``: ``_hand``, the futures set;
- ``request``: from ``_hand``'s return to the last ``step_chunk`` entry before
  the next flush sorts: every request's wake-up, its ``token_ids`` (``readback``
  is the part of it inside ``token_ids``, the first asker's blocking read) and
  its next call;
- ``loop``: from that entry to the next ``_sort``, the flush's two yields;
- ``cycle``: ``launch`` entry to ``launch`` entry, everything together.

``step`` is the device's: one launch of the bucket timed to
``block_until_ready`` of its logits, apart from the streams. One JSON line a
(vocabulary, streams) pair with the medians in ms, then a table.

    python3 tools/wave_turn_probe.py                      # on the chip: ~2 min
    python3 tools/wave_turn_probe.py --vocabs 512 --dim 128 --rounds 24 --context 64   # a smoke, anywhere
"""

import argparse
import asyncio
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BLOCK_TOKENS = 16
STAGES = (
    "sort", "assemble", "gate", "launch", "release", "resolve", "hand",
    "request", "readback", "loop", "cycle",
)


class Turn:
    """The stamps of the decoder's flushes, one dict a flush that launched."""

    def __init__(self, wave):
        self.flushes, self.cur, self.readback = [], None, 0.0
        self.last_entry = None
        for name in ("_sort", "_assemble", "launch", "_resolve", "_hand", "token_ids"):
            setattr(wave, name, self.around(name.lstrip("_"), getattr(wave, name)))
        step_chunk = wave.step_chunk

        def entered(*a, **kw):
            self.last_entry = time.perf_counter()
            return step_chunk(*a, **kw)

        wave.step_chunk = entered

    def around(self, stage, fn):
        def timed(*a, **kw):
            t0 = time.perf_counter()
            if stage == "sort":
                self.cur = {"last_entry": self.last_entry, "readback": self.readback}
                self.readback = 0.0
                self.flushes.append(self.cur)
            got = fn(*a, **kw)
            t1 = time.perf_counter()
            if stage == "token_ids":
                self.readback += t1 - t0
            elif stage == "hand":  # before the launch where no row was fed, else after
                self.cur["hand"], self.cur["hand_end"] = t1 - t0, t1
            else:
                self.cur[stage] = (t0, t1)
            return got

        return timed

    def medians(self, skip: int):
        """ms by stage over the flushes after the first ``skip`` that launched
        a wave and were followed by another."""
        rows = {s: [] for s in STAGES}
        flushes = [f for f in self.flushes if "launch" in f]
        for f, nxt in list(zip(flushes, flushes[1:]))[skip:]:
            for s in ("sort", "assemble", "launch", "resolve"):
                rows[s].append(f[s][1] - f[s][0])
            rows["gate"].append(f["launch"][0] - f["assemble"][1])
            rows["release"].append(f["resolve"][0] - f["launch"][1])
            rows["hand"].append(f["hand"])
            rows["request"].append(nxt["last_entry"] - f["hand_end"])
            rows["readback"].append(nxt["readback"])
            rows["loop"].append(nxt["sort"][0] - nxt["last_entry"])
            rows["cycle"].append(nxt["launch"][0] - f["launch"][0])
        return {s: round(statistics.median(v) * 1e3, 4) for s, v in rows.items()}, len(rows["cycle"])


def bare_decoder(config, params, num_blocks, max_req_blocks):
    import jax

    from infinistore_tpu.engine import ContinuousBatchingHarness, DeviceGate, WaveDecoder

    h = ContinuousBatchingHarness.__new__(ContinuousBatchingHarness)
    h.params, h.config = params, config
    h.caches = config.kv_spec(num_blocks).make_caches()
    h.max_req_blocks = max_req_blocks
    h.gate = DeviceGate()
    h.arriving = 0
    jax.block_until_ready(h.caches)
    return WaveDecoder(h)


async def streams(wave, tables, pos: int, rounds: int):
    async def one(table, pos=pos):
        tok = 1
        with wave.stream(table, pos + rounds - 1):
            for _ in range(rounds):
                rows = await wave.step_chunk([tok], [pos], table)
                tok, pos = int(wave.token_ids(rows)[0]), pos + 1

    await asyncio.gather(*(one(t) for t in tables))


def device_step_ms(wave, tables, pos: int, reps: int) -> float:
    """One launch of the bucket these tables ride, to the logits' landing."""
    import jax

    batch = [([1], [pos], t, None) for t in tables]
    took = []
    for _ in range(reps + 1):  # the first compiles and is dropped
        w = wave._assemble(batch)
        t0 = time.perf_counter()
        logits, _, _ = wave.launch(w.tokens, w.positions, w.row_of, w.meta, w.tables, w.wmeta)
        jax.block_until_ready(logits)
        took.append(time.perf_counter() - t0)
    return round(statistics.median(took[1:]) * 1e3, 4)


def probe(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from infinistore_tpu.models import LlamaConfig, llama

    device = jax.devices()[0]
    lines = []
    tokens = args.context + args.warm_rounds + args.rounds + 1
    mrb = 1 << (-(-tokens // BLOCK_TOKENS) - 1).bit_length()
    for vocab in args.vocabs:
        config = LlamaConfig(
            vocab=vocab, dim=args.dim, n_layers=args.layers, n_heads=args.dim // 128 or 1,
            n_kv_heads=1, ffn_dim=2 * args.dim, block_tokens=BLOCK_TOKENS, dtype=jnp.bfloat16,
        )
        params = llama.init_params(config, jax.random.PRNGKey(53))
        for n in args.streams:
            tables = [
                np.arange(1 + r * mrb, 1 + (r + 1) * mrb, dtype=np.int32)
                for r in range(n)
            ]
            wave = bare_decoder(config, params, 1 + n * mrb, mrb)
            step = device_step_ms(wave, tables, args.context, 10)
            # The bucket's program as a wave with fed rows runs it compiles here.
            asyncio.run(streams(wave, tables, args.context, args.warm_rounds))
            turn = Turn(wave)
            before, ahead = wave.waves, wave.waves_ahead
            asyncio.run(streams(wave, tables, args.context + args.warm_rounds, args.rounds))
            got, flushes = turn.medians(skip=2)
            line = {
                "vocab": vocab, "streams": n, "row_bucket": 1 << (n - 1).bit_length(),
                "device": f"{device.platform}:{device.device_kind}", "flushes": flushes,
                "waves": wave.waves - before, "waves_ahead": wave.waves_ahead - ahead,
                "row_slices": getattr(wave, "row_slices", None), "step": step, **got,
            }
            print(json.dumps(line), flush=True)
            lines.append(line)
    cols = ("vocab", "streams", "row_bucket", "step", *STAGES)
    print("| " + " | ".join(cols) + " |")
    print("|" + " --- |" * len(cols))
    for line in lines:
        print("| " + " | ".join(str(line[c]) for c in cols) + " |")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocabs", type=lambda s: [int(x) for x in s.split(",")], default=[32768, 200192])
    ap.add_argument("--streams", type=lambda s: [int(x) for x in s.split(",")], default=[1, 2, 3])
    ap.add_argument("--rounds", type=int, default=300)
    ap.add_argument("--warm-rounds", type=int, default=8)
    ap.add_argument("--context", type=int, default=8192, help="tokens a stream stands deep")
    ap.add_argument("--dim", type=int, default=1024)
    ap.add_argument("--layers", type=int, default=2)
    args = ap.parse_args()
    probe(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
