"""Draft providers for scheduler-scheduled speculative decoding.

The engine's spec phase is draft-agnostic: each iteration the scheduler asks
the provider for up to `k` proposed tokens per eligible slot, the engine
verifies every participating slot in ONE batched target forward, and the
longest matching prefix (plus the target's correction token) is emitted —
greedy output is token-identical to plain decode by construction.

Two providers:

- `NGramDraft` — retrieval speculation (vLLM's prompt-lookup / ngram
  speculator, REST's datastore shape): proposals come from suffix-matching
  the slot's own token history plus a bounded cross-request continuation
  store. Greedy decode is deterministic, so repeated traffic (the same
  workload the prefix cache serves on the prefill side) re-proposes earlier
  completions at near-full acceptance — and the draft costs ZERO device
  dispatches. Composes with prefix-cache hits trivially: the draft needs
  only token ids, which the admission path always has.

- `ModelDraft` — a draft MODEL proposes k tokens in one jitted lax.scan
  (the vLLM draft-worker shape): an external tiny model, the target itself
  (self-draft: the all-accept upper bound used in tests), or an EAGLE-style
  early-exit head built by `early_exit_draft` — the target's first j layers
  + final norm + output head, every parameter shared with the target, so
  the draft costs ~j/L of a target forward and no extra HBM. A slot
  admitted through a prefix-cache hit (or a PD-disagg transfer that carries
  `token_ids`) catches the draft cache up with one full-prompt draft
  prefill instead of downgrading to plain decode.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Dict, List, Optional

import numpy as np


class DraftProvider:
    """Interface the scheduler/engine drive. `propose` may return None (no
    speculation for that slot this iteration — it decodes plainly)."""

    kind = "none"
    k = 0

    def eligible(self, slot_idx: int, slot) -> bool:
        raise NotImplementedError

    def propose(self, slot_idx: int, slot) -> Optional[np.ndarray]:
        raise NotImplementedError

    def on_admit(self, slot_idx: int, prompt: List[int]):
        """Prompt fully attached/prefilled on the target; sync draft state."""

    def on_accept(self, slot_idx: int, slot, base_len: int,
                  proposed: np.ndarray, accepted: int):
        """Post-verify bookkeeping. base_len = target host_len before the
        round; accepted = length of the matched proposal prefix."""

    def on_plain_decode(self, slot_idx: int):
        """The slot advanced without the draft (plain/multi-step decode)."""

    def on_finish(self, slot_idx: int, slot):
        """The slot's request completed."""

    def stats(self) -> dict:
        return {"kind": self.kind, "k": self.k}


class NGramDraft(DraftProvider):
    """Zero-FLOP retrieval draft: propose the continuation that followed the
    history's trailing n-gram — in this request (prompt lookup) or in any
    recent request (cross-request store; greedy decode is deterministic, so
    repeats verify at full length).

    Matching is LONGEST-SUFFIX-first across several n-gram levels (REST's
    suffix-matching shape approximated with a small ladder of hash tables):
    a short n-gram aliases badly in self-similar text (a run of one token
    maps to many continuations), while a 16-gram match almost uniquely
    pins the position in the source sequence — measured on this repo's
    tiny-model streams, level-3-only accepts ~0.37 of proposals on repeat
    traffic where the ladder accepts ~1.0."""

    kind = "ngram"

    def __init__(self, *, k: int, n: int = 3, store_entries: int = 4096,
                 scan_window: int = 256, levels=(16, 8, 5)):
        self.k = max(1, int(k))
        self.n = max(1, int(n))  # the minimum (and prompt-lookup) level
        self.levels = tuple(sorted(
            {lv for lv in levels if lv > self.n} | {self.n}, reverse=True
        ))
        self._store_entries = max(0, int(store_entries))
        self._scan_window = max(self.n + 1, int(scan_window))
        # per level: trailing n-gram -> the (up to k) tokens that followed
        # it, most recent occurrence wins; bounded LRU per level so the
        # store cannot grow with traffic volume.
        self._stores: Dict[int, "OrderedDict[tuple, np.ndarray]"] = {
            lv: OrderedDict() for lv in self.levels
        }

    def eligible(self, slot_idx: int, slot) -> bool:
        return len(slot.history) >= self.n

    def propose(self, slot_idx: int, slot) -> Optional[np.ndarray]:
        hist = slot.history
        if self._store_entries:
            for lv in self.levels:          # longest suffix first
                if len(hist) < lv:
                    continue
                store = self._stores[lv]
                cont = store.get(tuple(hist[-lv:]))
                if cont is not None and len(cont):
                    store.move_to_end(tuple(hist[-lv:]))
                    return cont[: self.k]
        # Prompt-lookup fallback: the most recent earlier occurrence of the
        # trailing min-level n-gram inside this request's own history.
        n = self.n
        key = tuple(hist[-n:])
        lo = max(0, len(hist) - self._scan_window)
        for i in range(len(hist) - n - 1, lo - 1, -1):
            if tuple(hist[i:i + n]) == key:
                cont = hist[i + n: i + n + self.k]
                if cont:
                    return np.asarray(cont, np.int32)
                break
        return None

    def on_admit(self, slot_idx: int, prompt: List[int]):
        self._index(prompt)

    def on_finish(self, slot_idx: int, slot):
        self._index(slot.history)

    def _index(self, seq: List[int]):
        if not self._store_entries:
            return
        k = self.k
        for lv in self.levels:
            store = self._stores[lv]
            for j in range(len(seq) - lv):
                key = tuple(seq[j:j + lv])
                store.pop(key, None)
                store[key] = np.asarray(seq[j + lv: j + lv + k], np.int32)
            while len(store) > self._store_entries:
                store.popitem(last=False)

    def stats(self) -> dict:
        return {"kind": self.kind, "k": self.k, "n": self.n,
                "levels": list(self.levels),
                "store_entries": sum(len(s) for s in self._stores.values())}


class ModelDraft(DraftProvider):
    """Draft-model provider: k greedy proposals per slot in one lax.scan
    dispatch against the draft's own KV cache. Slot draft state (lengths,
    readiness, the pending all-accepted token whose KV must catch up) is
    host-native, mirroring the engine's slot bookkeeping discipline."""

    kind = "model"

    def __init__(self, cfg, params, *, k: int, num_slots: int, max_seq: int,
                 program: Callable, bucket: Callable):
        from ray_tpu.models import llama

        assert not cfg.scan_layers, "draft expects scan_layers=False layout"
        self.cfg = cfg
        self.params = params
        self.k = max(1, int(k))
        self.B = num_slots
        self.T = max_seq
        self._program = program     # engine's capped get-or-build helper
        self._bucket = bucket
        self.caches = llama.init_caches(cfg, self.B, self.T)
        self._host_lens = np.zeros((self.B,), np.int32)
        self._ready = [False] * self.B
        # all-k-accepted leaves one proposed token's kv missing from the
        # draft cache; it catches up at the next round's scan head.
        self._pending: List[Optional[int]] = [None] * self.B
        self._progs: Dict = {}

    # -- jitted draft programs ---------------------------------------------
    # Params and caches are explicit arguments (never closed over): a traced
    # closure would bake them into the compiled program as constants. Both
    # programs consume the draft's caches and return them written in place.
    def _propose_prog(self, params, caches, first_tok, t0, l, slot, *, k,
                      catchup):
        """Draft k greedy tokens in ONE program (lax.scan): the whole
        proposal costs one dispatch. With catchup=True the scan's first step
        ingests `first_tok` (the previous round's fully-accepted final
        proposal, whose kv never landed) and the chain restarts from t0 —
        the catch-up costs zero extra dispatches."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import llama

        dcfg = self.cfg
        slot_caches = [(c[0][slot][None], c[1][slot][None]) for c in caches]
        steps = k + 1 if catchup else k

        def step(carry, idx):
            tok, sc, pos = carry
            logits, new_sc = llama._forward_cached(
                params, dcfg, tok[None, None], pos[None, None], sc,
                pos[None], lora=None, adapter_ids=None,
            )
            nxt = jnp.argmax(logits[0, 0]).astype(jnp.int32)
            if catchup:
                nxt = jnp.where(idx == 0, t0, nxt)  # restart the chain at t0
            return (nxt, new_sc, pos + 1), nxt

        (_tok, out_slot, _pos), toks = jax.lax.scan(
            step, (first_tok, slot_caches, l), jnp.arange(steps)
        )
        if catchup:
            toks = toks[1:]
        return toks, llama._scatter_slot_caches(caches, out_slot, slot)

    def _prefill_prog(self, params, caches, tokens, slot):
        """Prefill the DRAFT cache on the (padded) whole prompt: spec decode
        needs the draft's kv history in lockstep with the target's — this is
        also the cache-hit/PD catch-up path, since the draft never holds
        another engine's attached prefix rows."""
        import jax.numpy as jnp

        from ray_tpu.models import llama

        S = tokens.shape[1]
        positions = jnp.arange(S)[None, :]
        slot_caches = [(c[0][slot][None], c[1][slot][None]) for c in caches]
        _logits, new_slot = llama._forward_cached(
            params, self.cfg, tokens, positions, slot_caches,
            jnp.zeros((1,), jnp.int32), lora=None, adapter_ids=None,
        )
        return llama._scatter_slot_caches(caches, new_slot, slot)

    # -- DraftProvider ------------------------------------------------------
    def eligible(self, slot_idx: int, slot) -> bool:
        return (
            self._ready[slot_idx]
            and int(self._host_lens[slot_idx]) + self.k + 1 <= self.T
        )

    def propose(self, slot_idx: int, slot) -> Optional[np.ndarray]:
        import jax
        import jax.numpy as jnp

        t0 = slot.tokens[-1]
        dlens = int(self._host_lens[slot_idx])
        pend = self._pending[slot_idx]
        catchup = pend is not None
        from ray_tpu.util.xprof import named

        prog = self._program(
            self._progs, ("propose", self.k, catchup),
            lambda: jax.jit(named(
                f"rt_draft_propose_k{self.k}" + ("_catchup" if catchup else ""),
                self._propose_prog, k=self.k, catchup=catchup), donate_argnums=(1,)),
        )
        toks_dev, self.caches = prog(
            self.params, self.caches,
            jnp.int32(pend if catchup else t0), jnp.int32(t0),
            jnp.int32(dlens), jnp.int32(slot_idx),
        )
        if catchup:
            self._host_lens[slot_idx] += 1  # the scan head landed pend's kv
            self._pending[slot_idx] = None
        # Per-round proposal sync: k tokens per pull, before the batched
        # verify assembles every slot's proposals host-side.
        return np.asarray(toks_dev)  # raylint: disable=RL603 (per-round k-token proposal pull)

    def on_admit(self, slot_idx: int, prompt: List[int]):
        import jax
        import jax.numpy as jnp

        bucket = self._bucket(len(prompt))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : len(prompt)] = prompt
        from ray_tpu.util.xprof import named

        prog = self._program(
            self._progs, ("dprefill", bucket),
            lambda: jax.jit(named(f"rt_draft_prefill_b{bucket}", self._prefill_prog),
                            donate_argnums=(1,)),
        )
        self.caches = prog(self.params, self.caches, jnp.asarray(padded),
                           jnp.int32(slot_idx))
        self._host_lens[slot_idx] = len(prompt)
        self._ready[slot_idx] = True
        self._pending[slot_idx] = None

    def on_accept(self, slot_idx: int, slot, base_len: int,
                  proposed: np.ndarray, accepted: int):
        if accepted == len(proposed) == self.k:
            self._host_lens[slot_idx] += self.k
            self._pending[slot_idx] = int(proposed[-1])
        else:
            # Rows past the accepted prefix are stale; the next round's scan
            # overwrites them starting at the correction token's row.
            self._host_lens[slot_idx] = base_len + accepted + 1
            self._pending[slot_idx] = None

    def on_plain_decode(self, slot_idx: int):
        # A plain step advances the target but not the draft: its proposals
        # would be garbage. Disable until the next admission re-prefills.
        self._ready[slot_idx] = False
        self._pending[slot_idx] = None

    def on_finish(self, slot_idx: int, slot):
        self._ready[slot_idx] = False
        self._pending[slot_idx] = None

    def stats(self) -> dict:
        return {
            "kind": self.kind, "k": self.k,
            "draft_layers": self.cfg.n_layers,
            "ready_slots": sum(1 for r in self._ready if r),
        }


def early_exit_draft(cfg, params, n_layers: int):
    """EAGLE-style early-exit head: the target's first `n_layers` layers +
    final norm + output head, sharing every parameter with the target (zero
    extra memory, ~n_layers/L of a target forward per proposed token).
    Returns (draft_cfg, draft_params) for ModelDraft."""
    import dataclasses

    if not 0 < n_layers < cfg.n_layers:
        raise ValueError(
            f"draft_layers must be in [1, {cfg.n_layers - 1}], got {n_layers}"
        )
    dcfg = dataclasses.replace(cfg, n_layers=n_layers)
    dparams = {"embedding": params["embedding"],
               "final_norm": params["final_norm"]}
    for i in range(n_layers):
        dparams[f"layer_{i}"] = params[f"layer_{i}"]
    if not cfg.tie_embeddings and "lm_head" in params:
        dparams["lm_head"] = params["lm_head"]
    return dcfg, dparams
