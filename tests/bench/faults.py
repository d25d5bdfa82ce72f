"""Faults planted in the program's timed path, for the tests that see the
check fail: each wraps the engine's compiled steps."""
import jax
import jax.numpy as jnp

import _bench_path  # noqa: F401
from bench import program  # noqa: F401  (puts the program on sys.path)
from repro.models import steps as ST
from repro.serving import engine as E


def plant(monkeypatch, fault: str) -> None:
    """``token``: every decode step's token for slot 0 is replaced by its
    successor, where it is produced. ``state``: the decode step hands back
    the cache it was given, so no decoded token's K/V is ever kept.
    ``prefill``: the prefill reads its first token off the position before
    the prompt's last, so only each request's first token is wrong."""
    real = E._jitted_steps

    def broken(cfg, kind, *a, **kw):
        prefill, chunked, suffix, decode, verify = real(cfg, kind, *a, **kw)
        if fault == "prefill":
            def early(step):
                return None if step is None else (
                    lambda p, t, li, *s: step(p, t, jnp.maximum(li - 1, 0),
                                              *s))
            return early(prefill), early(chunked), suffix, decode, verify
        if fault == "token":
            def decode_f(p, c, b):
                tok, cache, lp = decode(p, c, b)
                return tok.at[0].set((tok[0] + 1) % cfg.vocab), cache, lp
        elif fault == "state":
            plain = jax.jit(ST.make_decode_step(cfg))

            def decode_f(p, c, b):
                tok, _, lp = plain(p, c, b)
                return tok, jax.tree.map(jnp.copy, c), lp
        else:
            raise ValueError(fault)
        return prefill, chunked, suffix, decode_f, verify

    monkeypatch.setattr(E, "_jitted_steps", broken)
