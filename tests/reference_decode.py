"""The reference the serving paths are held to: greedy decode by
whole-sequence recompute.

No cache, no page table, no slot: every token comes from
`models/common.py: forward(params, cfg, tokens, positions, None, None,
lengths)` over the whole sequence so far, in the parameters' dtype — build
the engine under test with `dtype=jnp.float32`, so a near-tie between two
logits cannot flip between it and this. The sequence is padded to ONE
length a call (the causal mask keeps the pad out of every real position),
so a decode compiles one program however many tokens it makes.

Where a test compares SAMPLED streams, or every serving feature at once,
it compares the two serving paths that remain instead — pool-direct
(`attn="auto"`) against the gather view (`attn="dense"`), same seed.
"""

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from theroundtaible_tpu.engine.kvcache import scoped_slot
from theroundtaible_tpu.engine.models.common import forward


@lru_cache(maxsize=None)
def _next_token_fn(cfg):
    @jax.jit
    def next_token(params, tokens, length):
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
        logits, _ = forward(params, cfg, tokens, positions, None, None,
                            length, last_pos=length - 1)
        return jnp.argmax(logits[0, 0].astype(jnp.float32))
    return next_token


def greedy_decode(engine, prompt_ids, max_new: int) -> list[int]:
    """The tokens a greedy decode of `prompt_ids` makes on the engine's
    own parameters, up to eos (left out, as the serving paths leave it
    out) or `max_new`."""
    cfg = engine.cfg
    seq = list(prompt_ids)
    width = -(-(len(seq) + max_new) // 64) * 64
    step = _next_token_fn(cfg)
    out: list[int] = []
    for _ in range(max_new):
        tokens = np.full((1, width), engine.tokenizer.pad_id, np.int32)
        tokens[0, :len(seq)] = seq
        tok = int(step(engine.params, jnp.asarray(tokens),
                       jnp.asarray([len(seq)], jnp.int32)))
        if tok == engine.tokenizer.eos_id:
            break
        out.append(tok)
        seq.append(tok)
    return out


def assert_greedy(engine, turns, max_new: int, **kw) -> list[list[int]]:
    """Serve `turns` ([(slot name, text or ids)]) as one batch and hold
    every row to the cache-free decode, token for token: the text the
    engine returned, and what it committed to the slot (prompt + every
    token it fed back: all but the last). -> the prompts' ids."""
    ids = [list(p) if isinstance(p, list) else engine.tokenizer.encode(p)
           for _name, p in turns]
    texts = engine.generate_batch(
        [(name, row) for (name, _p), row in zip(turns, ids)],
        max_new_tokens=max_new, **kw)
    for (name, _p), row, text in zip(turns, ids, texts):
        slot = engine.kv._slots[scoped_slot(kw.get("session"), name)]
        assert slot.tokens[:len(row)] == row, \
            f"{name}: the prompt was truncated; give one that fits"
        want = greedy_decode(engine, row, max_new)
        assert text == engine.tokenizer.decode(want), name
        assert slot.tokens == row + want[:-1], name
    return ids
