"""Batch-invariant sampling: per-request stochastic decoding on the engine.

The engine was greedy-only; this module adds the full per-request sampling
surface (temperature, top-k, top-p nucleus, repetition penalty, stop
sequences, seeds) as ONE batched step that the decode/prefill executables
share across every parameter mix — param application is masked and
vectorized, so a batch mixing greedy and sampled rows still dispatches a
single OPQ program per step (the flag-audit invariant holds).

The load-bearing property is **batch invariance**: randomness is derived
counter-style from ``(request_seed, absolute_position)`` via
``jax.random.fold_in``, never from batch-level state, so a seeded request
emits the *same* token stream no matter which batchmates share its decode
step, which slot it lands in, which cache backend holds its K/V, or whether
a router drain hands it off mid-stream. This extends the repo's bit-identity
invariant family from greedy to stochastic decoding.

Stop sequences are matched host-side over the *generated* tokens only (the
prompt never triggers a stop); matching is suffix-based each step so a stop
spanning a decode-step boundary (or a speculative window) still fires.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SamplingParams", "GREEDY", "stack_params", "sample_tokens",
    "choose_tokens", "stop_match", "logprob_info", "TOP_LOGPROBS",
    "sampling_to_wire", "sampling_from_wire",
]

# Device-side top-K width for per-token logprob capture. Fixed so the
# decode/prefill executables stay ONE program regardless of what any
# request asked for (the serve API trims to the requested top_logprobs
# host-side; requests asking for more than this are rejected at the door).
TOP_LOGPROBS = 5


def _norm_stop(stop) -> Tuple[Tuple[int, ...], ...]:
    """Normalize stop sequences to a tuple of non-empty int tuples."""
    if stop is None:
        return ()
    if isinstance(stop, (int, np.integer)):
        stop = ((int(stop),),)
    out = []
    for seq in stop:
        if isinstance(seq, (int, np.integer)):
            seq = (int(seq),)
        seq = tuple(int(t) for t in seq)
        if not seq:
            raise ValueError("empty stop sequence")
        out.append(seq)
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decoding parameters.

    ``temperature <= 0`` means greedy (argmax) — the default — so a plain
    ``SamplingParams()`` is exactly the engine's historical behaviour.
    ``top_k <= 0`` disables top-k; ``top_p >= 1.0`` disables nucleus
    filtering; ``repetition_penalty == 1.0`` is a no-op. ``stop`` is a
    sequence of token-id sequences matched against generated tokens.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    repetition_penalty: float = 1.0
    seed: int = 0
    stop: Tuple[Tuple[int, ...], ...] = ()

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not (0.0 < self.top_p <= 1.0):
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.repetition_penalty <= 0:
            raise ValueError(
                f"repetition_penalty must be > 0, got {self.repetition_penalty}")
        object.__setattr__(self, "stop", _norm_stop(self.stop))

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


GREEDY = SamplingParams()


def stack_params(sps: Sequence[Optional[SamplingParams]],
                 presence: np.ndarray) -> Dict[str, jnp.ndarray]:
    """Stack per-slot SamplingParams into the batched arrays sample_tokens
    consumes. ``presence`` is the host-side (B, vocab_padded) bool array of
    token ids already seen by each slot (prompt + generated), used by the
    repetition penalty. ``None`` entries mean greedy (empty slots / legacy
    callers)."""
    sps = [sp if sp is not None else GREEDY for sp in sps]
    return {
        "temperature": jnp.asarray([sp.temperature for sp in sps], jnp.float32),
        "top_k": jnp.asarray([sp.top_k for sp in sps], jnp.int32),
        "top_p": jnp.asarray([sp.top_p for sp in sps], jnp.float32),
        "rep_penalty": jnp.asarray(
            [sp.repetition_penalty for sp in sps], jnp.float32),
        "seed": jnp.asarray([sp.seed for sp in sps], jnp.uint32),
        "greedy": jnp.asarray([sp.greedy for sp in sps], bool),
        "presence": jnp.asarray(presence, bool),
    }


def _gumbel_rows(seed: jnp.ndarray, position: jnp.ndarray,
                 vocab: int) -> jnp.ndarray:
    """(B,) seed x (B,) position -> (B, vocab) Gumbel noise, a pure function
    of each row's (seed, position) — the batch-invariance keystone."""

    def one(s, p):
        key = jax.random.fold_in(jax.random.PRNGKey(s), p)
        return jax.random.gumbel(key, (vocab,), jnp.float32)

    return jax.vmap(one)(seed, position)


def sample_tokens(logits: jnp.ndarray, sp: Dict[str, jnp.ndarray],
                  positions: jnp.ndarray) -> jnp.ndarray:
    """One batched, batch-invariant sampling step.

    logits: (B, vocab_padded) last-position logits (any float dtype).
    sp: stacked params from stack_params.
    positions: (B,) int32 absolute position of the token being emitted —
    the randomness counter.

    Greedy rows take a plain argmax on the raw (cast) logits — bit-identical
    to the historical greedy path. Sampled rows apply repetition penalty,
    temperature, top-k, top-p, then Gumbel-max with counter-derived noise.
    All rows run through one executable; the mix is masked, not branched
    per-row.

    Traced under the named scope ``sample``, but for the greedy argmax: the
    greedy branch of the ``cond`` below passes it through, and XLA names
    that branch's parameter after the scope of the value, so a scope on it
    would change the compiled program beyond its metadata.
    """
    with jax.named_scope("sample"):
        logits = logits.astype(jnp.float32)
    B, V = logits.shape
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    @jax.named_scope("sample")
    def sampled(_):
        # Repetition penalty (CTRL-style) over the presence mask.
        rep = sp["rep_penalty"][:, None]
        seen = sp["presence"]
        pen = jnp.where(logits > 0, logits / rep, logits * rep)
        l = jnp.where(seen, pen, logits)
        # Temperature.
        l = l / jnp.maximum(sp["temperature"], 1e-6)[:, None]
        # Sort once, apply top-k and top-p in sorted space.
        srt = jnp.sort(l, axis=-1)[:, ::-1]
        col = jnp.arange(V)[None, :]
        k = jnp.clip(sp["top_k"], 0, V)[:, None]
        in_k = (k <= 0) | (col < k)
        probs = jax.nn.softmax(jnp.where(in_k, srt, -jnp.inf), axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        top_p = sp["top_p"][:, None]
        keep = in_k & (((cum - probs) < top_p) | (top_p >= 1.0))
        keep = keep.at[:, 0].set(True)
        # Threshold back to unsorted space: allowed = logit >= smallest kept.
        thresh = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1)[:, None]
        allowed = l >= thresh
        g = _gumbel_rows(sp["seed"], positions.astype(jnp.int32), V)
        return jnp.argmax(jnp.where(allowed, l + g, -jnp.inf),
                          axis=-1).astype(jnp.int32)

    # Skip the whole sampled pipeline when every row is greedy (the common
    # serving default pays nothing).
    with jax.named_scope("sample"):
        tok = jax.lax.cond(jnp.any(~sp["greedy"]), sampled,
                           lambda _: greedy_tok, operand=None)
        return jnp.where(sp["greedy"], greedy_tok, tok)


def choose_tokens(row: jnp.ndarray, sampling: Optional[Dict[str, jnp.ndarray]],
                  positions) -> jnp.ndarray:
    """Logits row -> token, for the step builders: greedy argmax when no
    sampling state is threaded (legacy/test callers), the batched sampler
    otherwise. ``positions`` may be scalar (broadcast over the batch).
    Traced under the named scope ``sample``."""
    if sampling is None:
        with jax.named_scope("sample"):
            return jnp.argmax(row, axis=-1).astype(jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    if positions.ndim == 0:
        positions = jnp.broadcast_to(positions, (row.shape[0],))
    return sample_tokens(row, sampling, positions)


def logprob_info(row: jnp.ndarray, chosen: jnp.ndarray,
                 vocab: int) -> Dict[str, jnp.ndarray]:
    """Per-token logprob capture for the serve API: the log-softmax
    probability of the CHOSEN token (sampled or greedy) plus the top
    ``TOP_LOGPROBS`` alternatives, computed on the same logits row the
    token choice used — no second forward, no second executable.

    row: (..., vocab_padded) logits; chosen: (...,) int token ids.
    Padded vocab columns are masked to -inf BEFORE the softmax so the
    distribution is over the real vocabulary (pad logits are unspecified).
    Returns {"lp": (...,) f32, "top_ids": (..., K) i32, "top_lps":
    (..., K) f32}. Traced under the named scope ``sample``.
    """
    with jax.named_scope("sample"):
        row = row.astype(jnp.float32)
        V = row.shape[-1]
        real = jnp.arange(V) < vocab
        lp = jax.nn.log_softmax(jnp.where(real, row, -jnp.inf), axis=-1)
        chosen_lp = jnp.take_along_axis(
            lp, chosen[..., None].astype(jnp.int32), axis=-1)[..., 0]
        top_lps, top_ids = jax.lax.top_k(lp, TOP_LOGPROBS)
        return {"lp": chosen_lp, "top_ids": top_ids.astype(jnp.int32),
                "top_lps": top_lps}


def sampling_to_wire(sp: Optional[SamplingParams]) -> Optional[Dict]:
    """SamplingParams -> plain JSON/msgpack-able dict (transport frames)."""
    if sp is None:
        return None
    return {
        "temperature": sp.temperature, "top_k": sp.top_k, "top_p": sp.top_p,
        "repetition_penalty": sp.repetition_penalty, "seed": sp.seed,
        "stop": [list(seq) for seq in sp.stop],
    }


def sampling_from_wire(d: Optional[Dict]) -> Optional[SamplingParams]:
    """Inverse of :func:`sampling_to_wire` (worker side of the transport)."""
    if d is None:
        return None
    return SamplingParams(
        temperature=float(d.get("temperature", 0.0)),
        top_k=int(d.get("top_k", 0)), top_p=float(d.get("top_p", 1.0)),
        repetition_penalty=float(d.get("repetition_penalty", 1.0)),
        seed=int(d.get("seed", 0)),
        stop=tuple(tuple(int(t) for t in seq) for seq in d.get("stop", ())))


def stop_match(tokens: Sequence[int],
               stop: Tuple[Tuple[int, ...], ...]) -> Optional[Tuple[int, ...]]:
    """Suffix-match generated tokens against stop sequences; returns the
    matched sequence (or None). Called host-side each harvest, so a stop
    spanning a step boundary fires as soon as its last token lands."""
    if not stop:
        return None
    toks = tuple(tokens)
    for seq in stop:
        n = len(seq)
        if n <= len(toks) and toks[-n:] == seq:
            return seq
    return None
