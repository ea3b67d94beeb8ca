"""The replica of a cell whose model keeps a recurrent state a decode slot:
``BenchLLMServer`` with a check that compares the STATE as well as the logits.

``BenchLLMServer.check_numerics`` compares logits; a state kept in a lower
precision than the configuration states hardly moves them (PERF.md section 7,
after PR 48: one rounding of the state a position lies far under the bf16
activations' own).  Here the state is a third of what a decode step moves, so
a narrower state is the first illegitimate speed-up a later change would
find.  This check drives the same two sequences through the engine's own two
programs (the consuming views, on the engine's own pools, slot 0) and holds,
beside the logits of the prefill and eight token steps:

- the slot's state rows, unfolded, after the prefill and after the eight
  steps, against the states the reference hands out after those positions
  (``states_after``), layer by layer, to ``numerics.state_rtol``;
- the slot's convolution tail at the same two moments against the
  reference's last inputs, to ``numerics.tail_rtol``;
- the state pool's dtype, read from the engine's own pools, against
  ``numerics.state_dtype``: whatever the two errors can or cannot see (bf16
  activations feed both sides), a pool of another type is not the
  configuration.

``Session.deploy`` has no hook for a family's own check, so the check comes
as a subclass that overrides ``check_numerics`` alone and returns the same
keys and more (``generators/closed_loop_serve_states.py`` binds it).
"""

from __future__ import annotations

import time

from benchmark.replica import BenchLLMServer, device_report, seeded_key

STEPS = 8


def slot_rows(record_config, vp, slot: int = 0):
    """What slot ``slot`` keeps, as the reference hands it out: the states
    unfolded to [layers, heads, value_dim, key_dim] float32 and the tails
    [layers, K - 1, channels] float32, on the host."""
    import jax
    import numpy as np
    from ray_tpu.ops.linear_attention import unfold_state
    cfg = record_config
    states = jax.vmap(lambda folded: unfold_state(
        folded, cfg.linear_heads, cfg.linear_value_dim))(vp.state[:, slot])
    tails = vp.conv[:, slot].astype("float32")
    return (np.asarray(states).swapaxes(-1, -2),
            np.asarray(tails).reshape(tails.shape[0], cfg.linear_conv - 1,
                                      -1))


def drive(eng, tokens, prompt_len, steps=STEPS):
    """Prefill ``tokens[:prompt_len]`` (padded to ``max_prompt_len``) into
    slot 0 and step the next ``steps`` tokens, by the engine's own two
    programs (the consuming views, on the engine's own pools).  Returns (the
    logits of positions ``prompt_len - 1 .. prompt_len + steps - 1`` [steps +
    1, V], slot 0's rows (``slot_rows``) after the prefill and after the
    steps, the state pool's dtype)."""
    import numpy as np
    cfg, model = eng.config, eng.model_config
    table = np.zeros((cfg.max_batch, eng._maxp), np.int32)
    table[0] = np.arange(1, eng._maxp + 1)
    padded = np.zeros((1, cfg.max_prompt_len), np.int32)
    padded[0, :prompt_len] = tokens[:prompt_len]
    logits, kp, vp = eng._prefill(
        eng._params, padded, np.int32(prompt_len), eng._k_pages,
        eng._v_pages, table[:1])
    got, rows = [np.asarray(logits[0])], [slot_rows(model, vp)]
    tok = np.zeros((cfg.max_batch,), np.int32)
    pos = np.zeros((cfg.max_batch,), np.int32)
    for at in range(prompt_len, prompt_len + steps):
        tok[0], pos[0] = tokens[at], at
        logits, kp, vp = eng._decode(eng._params, tok, pos, kp, vp, table)
        got.append(np.asarray(logits[0]))
    rows.append(slot_rows(model, vp))
    return np.stack(got), rows, vp.state.dtype


def rel_errs(got, want):
    """The relative Frobenius error of every layer's rows, largest first in
    ``max``: (max, [a layer])."""
    import numpy as np
    errs = [float(np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30))
            for g, w in zip(got, want)]
    return max(errs), errs


class StatesBenchLLMServer(BenchLLMServer):
    def check_numerics(self) -> dict:
        import jax
        import jax.numpy as jnp
        import numpy as np
        eng, cfg = self._engine, self._engine.config
        started = time.perf_counter()
        key = seeded_key(self._seed + 1)
        lengths = (cfg.max_prompt_len // 8 + 5, cfg.max_prompt_len // 16 + 3)
        reference = jax.jit(
            lambda p, t, after: self._family.reference_forward(
                p, t, self._config, states_after=after))
        logit_errs, state_errs, tail_errs, by_layer = [], [], [], []
        for n, prompt_len in enumerate(lengths):
            tokens = np.asarray(jax.random.randint(
                jax.random.fold_in(key, n), (prompt_len + STEPS,), 0,
                self._model.vocab_size), np.int32)
            # one shape for both: causal, so what follows changes nothing
            padded = np.zeros((1, max(lengths) + STEPS), np.int32)
            padded[0, :len(tokens)] = tokens
            want, kept = reference(
                eng._params, padded,
                jnp.asarray([prompt_len, prompt_len + STEPS], jnp.int32))
            want = np.asarray(want[0])
            want_rows = (np.asarray(kept["state"])[:, :, 0],
                         np.asarray(kept["tail"])[:, :, 0])
            del kept
            got, rows, pool_dtype = drive(eng, tokens, prompt_len)
            want = want[prompt_len - 1:len(tokens)]
            logit_errs.append(float(np.linalg.norm(got - want)
                                    / np.linalg.norm(want)))
            for moment, (states, tails) in enumerate(rows):
                worst, each = rel_errs(states, want_rows[0][:, moment])
                state_errs.append(worst)
                by_layer.append(each)
                tail_errs.append(rel_errs(tails, want_rows[1][:, moment])[0])
        self._phases["check_numerics_s"] = time.perf_counter() - started
        # the tolerances are the configuration's, written there with reasons
        limits = self._config["numerics"]
        return {"device": device_report(), "logits_rel_err": logit_errs,
                "rtol": limits["logits_rtol"], "positions": STEPS + 1,
                # a sequence's two moments: after the prefill, after the steps
                "state_rel_err": state_errs,
                "state_rtol": limits["state_rtol"],
                "state_rel_err_by_layer": by_layer,
                "tail_rel_err": tail_errs, "tail_rtol": limits["tail_rtol"],
                "state_dtype": str(pool_dtype),
                "state_dtype_stated": limits["state_dtype"],
                "state_itemsize": pool_dtype.itemsize,
                "state_itemsize_stated": jnp.dtype(
                    limits["state_dtype"]).itemsize,
                "ok": max(logit_errs) <= limits["logits_rtol"]
                and max(state_errs) <= limits["state_rtol"]
                and max(tail_errs) <= limits["tail_rtol"]
                and str(pool_dtype) == limits["state_dtype"]}
