"""xlstm's decode step updates its recurrent state in place.

``XLSTM.decode_step`` carries the stacked mLSTM and sLSTM state through its
loops and writes each block's slice back where it lies, so the jitted,
donated decode neither slices the stacked state out as a scan input, nor
builds a fresh state-sized output, nor copies it at the end.  These tests
check the compiled program for that, and check the served tokens and state
against the nested-scan form the step replaced.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs import get_arch
from repro.launch.serve import serve_run_config
from repro.launch.step_fns import (greedy_decode, greedy_prefill,
                                   make_decode_step, make_prefill_step)
from repro.models import layers as L
from repro.models.xlstm import _mlstm_qkvif, slstm_apply

F32 = jnp.float32
XLSTM = "xlstm-350m"


def _producers(hlo: str, shape) -> set:
    """Op kinds of the HLO instructions whose result is f32 ``shape``."""
    pat = re.compile(r"= f32\[" + ",".join(map(str, shape)) + r"\]\S* ([\w-]+)\(")
    return {m.group(1) for m in pat.finditer(hlo)}


def test_served_decode_writes_the_state_in_place():
    """The donated greedy decode of xlstm-350m at its published widths (a
    small vocabulary, batch 2): nothing copies, broadcasts or slices out
    the stacked mLSTM ``C`` or one group's stack of it, the cache it
    returns aliases the donated one, and the state stays float32."""
    b = 2
    cfg = dataclasses.replace(get_arch(XLSTM), vocab_size=512)
    decode, model = make_decode_step(
        serve_run_config(cfg, batch=b, prompt_len=16))
    params = jax.eval_shape(model.init, jax.random.key(0))
    cache = jax.eval_shape(functools.partial(model.init_cache, b, 16))
    tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    step = jax.jit(greedy_decode(decode), donate_argnums=(1,))
    compiled = step.lower(params, cache, tok, tok).compile()
    hlo = compiled.as_text()

    full = cache["mlstm"]["C"].shape
    assert full == (4, 5, b, 4, 256, 256)
    # the state is written (a slice update, alone or fused) ...
    assert _producers(hlo, full) & {"dynamic-update-slice", "fusion"}
    # ... and never moved whole
    for shape in (full, full[1:]):
        moved = _producers(hlo, shape) & {"copy", "broadcast", "dynamic-slice"}
        assert not moved, (shape, moved)

    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes
    _, _, out = jax.eval_shape(step, params, cache, tok, tok)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), out) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), cache)
    assert {a.dtype for a in jax.tree.leaves(out)} == {jnp.dtype(F32)}


# --------------------------------------------- the nested-scan reference

def _scan_mlstm_decode(cfg, p, x, state):
    """The one-token mLSTM update with the outer product as an einsum."""
    b, _, d = x.shape
    q, k, v, ig, fg, z = _mlstm_qkvif(cfg, p, x)
    q, k, v = (a[:, 0].astype(F32) for a in (q, k, v))
    i_t = ig[:, 0]
    f_t = jnp.exp(fg[:, 0])
    c = state["C"].astype(F32) * f_t[:, :, None, None] + \
        jnp.einsum("bhd,bhe,bh->bhde", k, v, i_t)
    n = state["n"].astype(F32) * f_t[:, :, None] + k * i_t[:, :, None]
    num = jnp.einsum("bhd,bhde->bhe", q, c)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q, n)), 1.0)
    y = (num / den[..., None]).reshape(b, 1, d).astype(x.dtype)
    y = y * jax.nn.silu(z.astype(F32)).astype(x.dtype)
    return x + jnp.einsum("bsd,de->bse", y, p["w_down"]), {"C": c, "n": n}


def _scan_decode_step(cfg, params, cache, tokens, pos):
    """The decode step that passed the state through nested scans as
    ``xs`` and took the new state back as ``ys``."""
    x = L.embed_lookup(params["embed"], tokens)

    def group(xc, gp):
        mp, sp, mst, sst = gp

        def m_body(xi, inp):
            mpi, sti = inp
            return _scan_mlstm_decode(cfg, mpi, xi, sti)
        xc, new_m = lax.scan(m_body, xc, (mp, mst))
        xc, new_s = slstm_apply(cfg, sp, xc, state=sst, return_state=True)
        return xc, (new_m, new_s)

    x, (new_m, new_s) = lax.scan(
        group, x, (params["mlstm"], params["slstm"],
                   cache["mlstm"], cache["slstm"]))
    x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return L.unembed(cfg, params["embed"], x), {"mlstm": new_m,
                                                "slstm": new_s}


def test_decode_matches_the_nested_scan_reference():
    """Two groups of two mLSTM blocks and an sLSTM block: eight greedy
    decode steps from one prefill give the reference's tokens, and a state
    within 1e-6 of it, relative."""
    b, prompt_len, steps = 2, 8, 8
    cfg = dataclasses.replace(get_arch(XLSTM).reduced(), n_layers=6,
                              slstm_every=3)
    run = serve_run_config(cfg, batch=b, prompt_len=prompt_len)
    prefill, model = make_prefill_step(run)
    decode, _ = make_decode_step(run)
    assert (model.n_groups, model.m_per_group) == (2, 2)
    params = model.init(jax.random.key(0))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (b, prompt_len), dtype=np.int32))
    tok, pos, cache = jax.jit(greedy_prefill(prefill), static_argnums=(2,))(
        params, {"tokens": tokens}, steps)

    runs = {}
    for name, fn in (("inplace", decode),
                     ("scan", functools.partial(_scan_decode_step, cfg))):
        step = jax.jit(greedy_decode(fn))
        t, p, c, toks = tok, pos, cache, []
        for _ in range(steps):
            t, p, c = step(params, c, t, p)
            toks.append(np.asarray(t))
        runs[name] = np.concatenate(toks, axis=1), c

    (got, got_state), (want, want_state) = runs["inplace"], runs["scan"]
    np.testing.assert_array_equal(got, want)
    assert jax.tree.structure(got_state) == jax.tree.structure(want_state)
    for g, w in zip(jax.tree.leaves(got_state), jax.tree.leaves(want_state)):
        assert g.dtype == w.dtype == F32
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6,
                                   atol=0)
