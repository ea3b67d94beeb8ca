"""The replica of a cell whose model generates by diffusion over blocks:
``BenchLLMServer`` with a check of its own.

``BenchLLMServer.check_numerics`` decodes one token at a time against a
causal reference; neither holds for a model whose decode step denoises a
block of positions and whose attention runs both ways inside a block.
``Session.deploy`` has no hook for a family's own check, so the check comes
as a subclass that overrides ``check_numerics`` alone and returns the same
keys (``generators/closed_loop_serve_blocks.py`` binds it).
"""

from __future__ import annotations

import time

from benchmark.replica import BenchLLMServer, device_report, seeded_key

BLOCKS = 3                           # blocks driven a sequence


def sequences(key, max_prompt_len: int, B: int, vocab: int):
    """The check's two seeded sequences, [(tokens, prompt length)]: prompt
    lengths that are no multiples of the block length (the first block
    holds a tail), each followed by ``BLOCKS`` blocks to drive."""
    import jax
    import numpy as np
    lengths = [n + (n % B == 0) for n in (max_prompt_len // 16 + 5,
                                          max_prompt_len // 32 + 2)]
    longest = (max(lengths) // B + BLOCKS) * B
    return [(np.asarray(jax.random.randint(
        jax.random.fold_in(key, n), (longest,), 0, vocab), np.int32), length)
        for n, length in enumerate(lengths)]


def block_states(block, masked):
    """The three states a block is driven through, as (tokens, masked)
    with ``mask`` ids to be filled in by the caller: every position that is
    to be generated masked; the first half of those unmasked; none masked
    (the commit pass).  ``masked`` [B] marks the positions to generate."""
    import numpy as np
    at = np.flatnonzero(masked)
    half = masked.copy()
    half[at[:(len(at) + 1) // 2]] = False
    return [(block, masked), (block, half), (block, np.zeros_like(masked))]


def drive(eng, params, tokens, prompt_len, mask_token, decode=None):
    """Prefill ``tokens[:prompt_len]``'s whole blocks and drive ``BLOCKS``
    blocks of the rest through ``block_states`` by the engine's own two
    programs (the consuming views, on the engine's own pools, slot 0):
    yields (the sequence as it stood for a pass, the pass's logits [B, V],
    the block's first position).  The blocks after the first read what the
    commits before them left in the pages."""
    import numpy as np
    cfg, B = eng.config, eng.model_config.block_length
    decode = decode or eng._decode
    whole = prompt_len // B * B
    table = np.zeros((cfg.max_batch, eng._maxp), np.int32)
    table[0] = np.arange(1, eng._maxp + 1)
    padded = np.zeros((1, cfg.max_prompt_len), np.int32)
    padded[0, :whole] = tokens[:whole]
    _, kp, vp = eng._prefill(params, padded, np.int32(whole),
                             eng._k_pages, eng._v_pages, table[:1])
    rows = (cfg.max_batch, B)
    end = np.zeros((cfg.max_batch,), np.int32)
    end[0] = whole + BLOCKS * B
    for pos0 in range(whole, whole + BLOCKS * B, B):
        block = tokens[pos0:pos0 + B]
        for passes, (block, masked) in enumerate(block_states(
                block, np.arange(pos0, pos0 + B) >= prompt_len)):
            state = (np.zeros(rows, np.int32), np.zeros(rows, np.bool_),
                     np.zeros(rows[:1], np.int32),
                     np.zeros(rows[:1], np.int32))
            state[0][0] = np.where(masked, mask_token, block)
            state[1][0], state[2][0], state[3][0] = masked, pos0, passes
            logits, kp, vp = decode(params, state, end, kp, vp, table)
            stood = np.array(tokens[:pos0 + B])
            stood[pos0:] = state[0][0]
            yield stood, np.asarray(logits[0]), pos0


class BlockBenchLLMServer(BenchLLMServer):
    def check_numerics(self) -> dict:
        """Prefill and then three blocks of two seeded sequences (prompt
        lengths no multiples of the block length), each block through
        fixed masked states (all masked, half unmasked, whole: the commit),
        by the engine's own two programs through the paged cache, against
        the reference's full forward over the prompt, the blocks committed
        so far and the block as it stands.  Blocks two and three read what
        commits one and two left in the pages.  Warms both programs up on
        the way; the engine's pools are left as they were."""
        import jax
        import numpy as np
        eng, cfg = self._engine, self._engine.config
        B = self._model.block_length
        started = time.perf_counter()
        reference = jax.jit(lambda p, t: self._family.reference_forward(
            p, t, self._config))
        errs, passes = [], 0
        for tokens, prompt_len in sequences(
                seeded_key(self._seed + 1), cfg.max_prompt_len, B,
                self._model.vocab_size):
            longest = len(tokens)
            got, want = [], []
            for stood, logits, pos0 in drive(
                    eng, eng._params, tokens, prompt_len,
                    self._model.mask_token):
                # one shape for all: causal over blocks, so what follows
                # the block changes nothing
                padded = np.zeros((longest,), np.int32)
                padded[:len(stood)] = stood
                want.append(np.asarray(
                    reference(eng._params, padded)[pos0:pos0 + B]))
                got.append(logits)
            passes = len(got)
            errs.append(float(np.linalg.norm(np.stack(got) - np.stack(want))
                              / np.linalg.norm(np.stack(want))))
        self._phases["check_numerics_s"] = time.perf_counter() - started
        rtol = self._config["numerics"]["logits_rtol"]
        return {"device": device_report(), "logits_rel_err": errs,
                "rtol": rtol, "positions": passes * B,
                "ok": max(errs) <= rtol}
