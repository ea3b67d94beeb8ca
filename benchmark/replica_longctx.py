"""The replica of a cell whose prompts run as chunks over the pages and whose
attention reads a selection: ``BenchLLMServer`` with a check of its own.

``BenchLLMServer.check_numerics`` prefills in one call a prompt an eighth of
``max_prompt_len`` long; neither reaches what this cell is for.  This one
drives, by the engine's own two programs, a prompt of ``prefill_chunk +
index_topk + 37`` positions (two chunks at the published sizes, every query
past position ``index_topk`` selecting, the second chunk reading what the
first left in the pages across the chunk's edge) and one of ``index_topk / 2
+ 3`` (nothing deselected), each followed by eight token steps, against the
reference's full forward, whose selection is a mask.  The two sequences are held to a limit each (the
selecting one's is the looser: ``numerics.why`` of the configuration says
what the stored keys' rounding does to a kept position in 1,600).  It reports
beside ``logits_rel_err`` the share of the positions the reference's queries kept
that the program's kept too, and how many each kept (the same number, or the
check fails), on the long sequence's token steps: the
program's selection is read from a second decode program traced with
``select_positions`` handing what it returns to the host (the same function,
the same arithmetic; the engine's own programs give the logits).

``Session.deploy`` has no hook for a family's own check, so the check comes
as a subclass that overrides ``check_numerics`` alone and returns the same
keys and two more (``generators/closed_loop_serve_longctx.py`` binds it).
"""

from __future__ import annotations

import contextlib
import time

from benchmark.replica import BenchLLMServer, device_report, seeded_key

STEPS = 8


def prompt_lengths(engine: dict, topk: int) -> tuple:
    """The check's two prompts: one past a chunk's edge AND past the
    selection's size (as far as ``max_prompt_len`` allows), one under half
    the selection's size."""
    chunk = engine.get("prefill_chunk") or engine["max_prompt_len"]
    return (min(chunk + topk + 37, engine["max_prompt_len"] - 3),
            topk // 2 + 3)


def drive(eng, params, tokens, prompt_len, steps=STEPS, decode=None):
    """Prefill ``tokens[:prompt_len]`` as the loop does (chunks of
    ``prefill_chunk``, each padded to its rung, into slot 0's pages 1, 2,
    ...) and step the next ``steps`` tokens, by the engine's own programs
    (the consuming views, on the engine's own pools): the logits of positions
    ``prompt_len - 1 .. prompt_len + steps - 1`` [steps + 1, V]."""
    import numpy as np
    from ray_tpu.serve.engine.engine import rung_for
    cfg = eng.config
    decode = decode or eng._decode
    table = np.zeros((cfg.max_batch, eng._maxp), np.int32)
    table[0] = np.arange(1, eng._maxp + 1)
    kp, vp = eng._k_pages, eng._v_pages
    chunk = cfg.prefill_chunk or prompt_len
    for start in range(0, prompt_len, chunk):
        width = min(chunk, prompt_len - start)
        padded = np.zeros((1, rung_for(eng._rungs, width)), np.int32)
        padded[0, :width] = tokens[start:start + width]
        logits, kp, vp = eng._prefill(
            params, padded, np.int32(width), kp, vp, table[:1], np.int32(0),
            *([np.int32(start)] if cfg.prefill_chunk else []))
    got = [np.asarray(logits[0])]
    tok = np.zeros((cfg.max_batch,), np.int32)
    pos = np.zeros((cfg.max_batch,), np.int32)
    for i in range(steps):
        tok[0], pos[0] = tokens[prompt_len + i], prompt_len + i
        logits, kp, vp = decode(params, tok, pos, kp, vp, table)
        got.append(np.asarray(logits[0]))
    return np.stack(got)


@contextlib.contextmanager
def told_selections(store: list):
    """While a decode program is traced inside this, ``select_positions``
    also hands what it returns for slot 0 to ``store``, a (positions,
    counted) pair a layer a step, in the order the layers run."""
    import importlib
    import jax
    import numpy as np
    # (``ray_tpu.ops`` names a function ``paged_attention`` too)
    ops = importlib.import_module("ray_tpu.ops.paged_attention")
    real = ops.select_positions

    def telling(scores, lengths, k):
        at, counted = real(scores, lengths, k)
        jax.debug.callback(
            lambda a, c: store.append((np.asarray(a), np.asarray(c))),
            at[0], counted[0], ordered=True)
        return at, counted
    ops.select_positions = telling
    try:
        yield
    finally:
        ops.select_positions = real


def common_share(told: list, keep, prompt_len: int, layers: int) -> tuple:
    """Of the positions the reference's queries kept (``keep`` [layers, S,
    S], rows ``prompt_len ..`` the token steps'), the share the program's
    kept too (``told``: a pair a layer a step, layers fastest); and how many
    each kept in all, (the program, the reference): a program that keeps one
    position fewer a query shares all it keeps."""
    import numpy as np
    kept = mine = common = 0
    for n, (at, counted) in enumerate(told):
        step, layer = divmod(n, layers)
        want = np.flatnonzero(keep[layer, prompt_len + step])
        kept += len(want)
        mine += int(counted.sum())
        common += len(np.intersect1d(want, at[counted]))
    return common / kept, (mine, kept)


class LongctxBenchLLMServer(BenchLLMServer):
    def check_numerics(self) -> dict:
        """See the module's docstring.  Warms the prefill's rungs and the
        decode program up on the way; the engine's pools are left as they
        were (what the check wrote is in pages no sequence holds)."""
        import jax
        import numpy as np
        from ray_tpu.models.serving import serving_model
        eng, cfg = self._engine, self._engine.config
        started = time.perf_counter()
        topk, layers = self._model.index_topk, self._model.num_layers
        lengths = prompt_lengths(self._config["engine"], topk)
        longest = max(lengths) + STEPS
        reference = jax.jit(lambda p, t, first: (
            self._family.reference_forward(
                p, t, self._config, rows=(first, STEPS + 1),
                with_selection=True)))
        key = seeded_key(self._seed + 1)
        errs, share, sizes = [], None, None
        for n, prompt_len in enumerate(lengths):
            tokens = np.asarray(jax.random.randint(
                jax.random.fold_in(key, n), (longest,), 0,
                self._model.vocab_size), np.int32)
            # one shape for both: causal, so what follows changes nothing
            want, keep = reference(eng._params, tokens,
                                   np.int32(prompt_len - 1))
            want = np.asarray(want)
            got = drive(eng, eng._params, tokens, prompt_len)
            errs.append(float(np.linalg.norm(got - want)
                              / np.linalg.norm(want)))
            if share is None:        # the long sequence: what the steps kept
                told = []
                with told_selections(told):
                    step = serving_model(self._family.ENGINE_MODEL,
                                         self._model).step
                    telling = jax.jit(
                        lambda p, tok, pos, kp, vp, pt: step(
                            p, self._model, tok, pos, kp, vp, pt),
                        donate_argnums=(3, 4))
                    again = drive(
                        eng, eng._params, tokens, prompt_len,
                        decode=lambda *a: eng._consuming(telling, *a))
                jax.effects_barrier()
                if np.linalg.norm(again - got) > 1e-2 * np.linalg.norm(got):
                    raise RuntimeError("the telling decode program gave "
                                       "other logits than the engine's own")
                share, sizes = common_share(told, np.asarray(keep),
                                            prompt_len, layers)
            del want, keep
        self._phases["check_numerics_s"] = time.perf_counter() - started
        # The limits are the configuration's, written there with their
        # reasons.  TWO for the logits: the sequence that selects carries
        # what the stored keys' rounding does to a few of its kept positions
        # (``numerics.why``) and has the looser one; the sequence under the
        # selection's size reads what every other latent cell reads, and its
        # limit is as tight as theirs.
        numerics = self._config["numerics"]
        rtol, selecting = (numerics["logits_rtol"],
                           numerics["logits_rtol_selecting"])
        least = numerics["selection_common_min"]
        return {"device": device_report(), "logits_rel_err": errs[1:],
                "rtol": rtol, "logits_rel_err_selecting": errs[0],
                "rtol_selecting": selecting, "positions": STEPS + 1,
                "prompt_lengths": list(lengths),
                "selection_common_share": share,
                "selection_common_min": least,
                "selected_positions": list(sizes),
                "ok": max(errs[1:]) <= rtol and errs[0] <= selecting
                and share >= least and sizes[0] == sizes[1]}
