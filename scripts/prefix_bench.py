"""Prefix-store microbench: multi-turn chat under slot churn, store on/off.

The scenario the slot-resident prefix cache loses: more concurrent
conversations than KV slots, each re-sending its whole history every turn.
Round-robining N conversations over S < N slots guarantees every slot is
reclaimed between a conversation's turns, so the automatic (tier-0) cache
never hits on follow-up turns — exactly the load where prefill capacity
matters. With ``prefix_store=host`` the released prefixes survive in host
RAM and follow-up turns restore them, prefilling only the tail.

Reports, per leg (store off / store on):

  - ``prefill_tokens``        prompt tokens actually prefilled on device
  - ``saved_tokens``          prompt tokens skipped (slot reuse + restores)
  - ``store_hits`` / ``store_restored_tokens`` / ``restore_ms_mean``
  - ``wall_s``                leg wall time
  - ``tokens_match``          every turn's sampled output identical across
                              legs (reuse is a scheduling optimization,
                              never a semantic change)

Usage:  python scripts/prefix_bench.py [--conversations N] [--slots S]
        [--turns T] [--new-tokens G] [--chunk C]
Prints one human-readable block and one machine-parsable JSON line.
``make prefix-bench`` runs it; tests/test_prefix_bench.py is the suite's
fast smoke over the same entry point.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# Runnable as `python scripts/prefix_bench.py` from a checkout without
# `pip install -e`: the repo root (not scripts/) must be importable.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(conversations: int = 5, slots: int = 2, turns: int = 3,
        new_tokens: int = 6, chunk: int = 16,
        store_bytes: int = 64 << 20) -> dict:
    """Drive ``conversations`` multi-turn chats round-robin over ``slots``
    KV slots, once without and once with the host prefix store; return the
    prefill/restore accounting. Conversations must outnumber slots or
    there is no churn to measure."""
    if conversations <= slots:
        raise ValueError(
            f"conversations ({conversations}) must exceed slots ({slots}) "
            "— without churn the slot-resident cache already wins and the "
            "store never fires")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.models.model_config import resolve_spec
    from quorum_tpu.ops.sampling import SamplerConfig

    # Window sized to the conversation growth so every turn fits:
    # initial 2·chunk history + per-turn (new_tokens + 5) user/reply tokens.
    need = 2 * chunk + turns * (new_tokens + 5) + new_tokens + 1
    max_seq = 64
    while max_seq < need:
        max_seq *= 2
    spec = resolve_spec("llama-tiny", {"max_seq": str(max_seq)})
    greedy = SamplerConfig(temperature=0.0)

    def user_tokens(conv: int, turn: int, n: int = 5) -> list[int]:
        return [(11 + 13 * conv + 7 * turn + 3 * i)
                % (spec.vocab_size - 1) + 1 for i in range(n)]

    out: dict = {"conversations": conversations, "slots": slots,
                 "turns": turns, "new_tokens": new_tokens,
                 "store_chunk": chunk}
    streams: dict[str, list[list[int]]] = {}

    for leg, store in (("off", None), ("on", "host")):
        eng = InferenceEngine(
            spec, decode_chunk=4, prefill_chunk=chunk, n_slots=slots,
            prefix_store=store, prefix_store_bytes=store_bytes,
        )
        histories = {c: [1 + (c * 17 + i * 7) % (spec.vocab_size - 1)
                         for i in range(2 * chunk)]
                     for c in range(conversations)}
        outputs: list[list[int]] = []
        prefilled = 0
        t0 = time.perf_counter()
        for turn in range(turns):
            for c in range(conversations):
                prompt = histories[c]
                saved0 = eng.prefix_tokens_saved + eng.prefix_store_tokens_restored
                res = eng.generate(prompt, max_new_tokens=new_tokens,
                                   sampler=greedy, seed=c)
                saved = (eng.prefix_tokens_saved
                         + eng.prefix_store_tokens_restored - saved0)
                prefilled += len(prompt) - saved
                outputs.append(res.token_ids)
                histories[c] = prompt + res.token_ids + user_tokens(c, turn)
            eng.drain_prefix_store()
        wall = time.perf_counter() - t0
        streams[leg] = outputs
        out[f"{leg}_wall_s"] = round(wall, 4)
        out[f"{leg}_prefill_tokens"] = prefilled
        out[f"{leg}_saved_tokens"] = (eng.prefix_tokens_saved
                                      + eng.prefix_store_tokens_restored)
        out[f"{leg}_store_hits"] = eng.prefix_store_hits
        out[f"{leg}_store_restored_tokens"] = eng.prefix_store_tokens_restored
        out[f"{leg}_restore_ms_mean"] = round(
            1000 * eng.prefix_store_restore_s / eng.prefix_store_hits, 3
        ) if eng.prefix_store_hits else 0.0
        if store:
            out["store_bytes_held"] = eng.prefix_store.bytes_held
            out["store_evictions"] = eng.prefix_store.n_evictions
        eng.shutdown()

    out["prefill_tokens_saved_by_store"] = (
        out["off_prefill_tokens"] - out["on_prefill_tokens"])
    out["tokens_match"] = streams["off"] == streams["on"]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--conversations", type=int, default=5)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--turns", type=int, default=3)
    ap.add_argument("--new-tokens", type=int, default=6)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--store-bytes", type=int, default=64 << 20)
    args = ap.parse_args()
    if args.conversations <= args.slots:
        ap.error("--conversations must exceed --slots (no churn otherwise)")
    m = run(args.conversations, args.slots, args.turns, args.new_tokens,
            args.chunk, args.store_bytes)
    print(f"prefix-store microbench (llama-tiny, {m['conversations']} "
          f"conversations over {m['slots']} slots, {m['turns']} turns):")
    for leg in ("off", "on"):
        print(f"  store {leg:>3}: {m[f'{leg}_prefill_tokens']} prompt tokens "
              f"prefilled, {m[f'{leg}_saved_tokens']} saved, "
              f"{m[f'{leg}_store_hits']} store hits, "
              f"wall {m[f'{leg}_wall_s']}s")
    print(f"  prefill tokens saved by the store: "
          f"{m['prefill_tokens_saved_by_store']}")
    print(f"  restored tokens: {m['on_store_restored_tokens']} "
          f"(mean restore {m['on_restore_ms_mean']} ms)")
    print(f"  token-for-token identical across legs: {m['tokens_match']}")
    print(json.dumps(m), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
