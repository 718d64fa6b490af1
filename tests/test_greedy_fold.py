"""Greedy sampling folded into the jitted prefill and decode
(``repro.launch.step_fns.greedy_prefill`` / ``greedy_decode``): the served
tokens are bitwise those of the logits steps followed by an eager
``argmax``, ``DecodeWorkload.step`` dispatches one program and does no
array math of its own, and the programs keep the names the benchmark's
trace readers select them by."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ft import DecodeWorkload
from repro.launch.serve import ReplicatedServer, serve_run_config
from repro.launch.step_fns import (greedy_decode, greedy_prefill,
                                   make_decode_step, make_prefill_step)

BATCH, PROMPT, STEPS = 2, 8, 4


def _prompts(vocab):
    return np.random.default_rng(0).integers(0, vocab, (BATCH, PROMPT),
                                             dtype=np.int32)


def _eager_tokens(srv, batch):
    """The logits steps, jitted, each followed by the eager sampling."""
    run = serve_run_config(srv.cfg, batch=BATCH, prompt_len=PROMPT)
    prefill = jax.jit(make_prefill_step(run)[0])
    decode = jax.jit(make_decode_step(run)[0])
    logits, cache = prefill(srv.params, batch)
    tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    pos = jnp.full((BATCH, 1), PROMPT, jnp.int32)
    toks = [tok]
    for _ in range(STEPS):
        logits, cache = decode(srv.params, cache, tok, pos)
        tok = jnp.argmax(logits[:, -1, :],
                         axis=-1)[:, None].astype(jnp.int32)
        pos = pos + 1
        toks.append(tok)
    return np.concatenate([np.asarray(t) for t in toks], axis=1), pos


@pytest.mark.parametrize("arch", ["whisper-tiny", "xlstm-350m", "qwen3-8b"])
def test_folded_tokens_equal_eager_sampling(arch):
    srv = ReplicatedServer(arch, batch=BATCH, prompt_len=PROMPT)
    wl = srv.workload(_prompts(srv.cfg.vocab_size))
    want, want_pos = _eager_tokens(srv, wl.batch)
    state = wl.init_state()
    for t in range(STEPS):
        state, _ = wl.step(state, t)
    got = np.concatenate([DecodeWorkload.tokens(state),
                          np.asarray(state["tok"])], axis=1)
    assert got.dtype == want.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(state["pos"]),
                                  np.asarray(want_pos))


class _Token:
    """A token that logs when it is copied to the host."""

    def __init__(self, log, value):
        self.log, self.value = log, value

    def __array__(self, dtype=None, copy=None):
        self.log.append("host_copy")
        return np.full((BATCH, 1), self.value, np.int32)


def test_step_is_one_decode_call_after_the_host_copy():
    log, calls = [], []
    params, cache, pos = object(), object(), object()
    tok = _Token(log, 7)
    returned = (_Token(log, 8), object(), object())

    def decode(*args):
        log.append("decode")
        calls.append(args)
        return returned

    wl = DecodeWorkload(params=params, prefill=None, decode=decode,
                        batch=None)
    state = {"cache": cache, "tok": tok, "pos": pos, "out": []}
    new, metrics = wl.step(state, 0)
    assert metrics is None
    assert log == ["host_copy", "decode"]
    assert len(calls) == 1
    assert all(a is b for a, b in zip(calls[0], (params, cache, tok, pos)))
    assert new["tok"] is returned[0]
    assert new["pos"] is returned[1]
    assert new["cache"] is returned[2]
    assert len(new["out"]) == 1 and state["out"] == []
    np.testing.assert_array_equal(new["out"][0], np.full((BATCH, 1), 7))


def test_init_state_unpacks_the_prefill():
    calls = []
    params, batch = object(), object()
    tok, pos, cache = object(), object(), object()

    def prefill(*args):
        calls.append(args)
        return tok, pos, cache

    wl = DecodeWorkload(params=params, prefill=prefill, decode=None,
                        batch=batch)
    state = wl.init_state()
    assert len(calls) == 1
    assert calls[0][0] is params and calls[0][1] is batch
    assert state["tok"] is tok and state["pos"] is pos
    assert state["cache"] is cache and state["out"] == []


@pytest.fixture(scope="module")
def qwen_server():
    return ReplicatedServer("qwen3-8b", batch=BATCH, prompt_len=PROMPT)


def _args(srv, name):
    """Abstract arguments of the server's prefill or decode."""
    batch = srv.workload(_prompts(srv.cfg.vocab_size)).batch
    if name == "prefill_step":
        return srv.params, batch
    _, _, cache = jax.eval_shape(srv.prefill, srv.params, batch)
    tok = jax.ShapeDtypeStruct((BATCH, 1), jnp.int32)
    return srv.params, cache, tok, tok


@pytest.mark.parametrize("name", ["decode_step", "prefill_step"])
def test_jitted_programs_keep_their_names(qwen_server, name):
    """The trace readers select ``jit_decode_step`` by name."""
    step = qwen_server.prefill if name == "prefill_step" \
        else qwen_server.decode
    assert f"jit_{name}" in step.lower(*_args(qwen_server, name)).as_text()
    wrap = greedy_prefill if name == "prefill_step" else greedy_decode
    assert wrap(lambda *a: a).__name__ == name


@pytest.mark.parametrize("name", ["decode_step", "prefill_step"])
def test_argmax_reads_the_logits_through_a_barrier(qwen_server, name):
    """Every path from the argmax back into the model passes the
    optimization barrier on the logits.  Without it the TPU compiler fuses
    the output projection into the argmax and chooses the token from the
    projection computed another way (``tests/test_tpu_compile.py`` checks
    the compiled v5e programs)."""
    run = serve_run_config(qwen_server.cfg, batch=BATCH, prompt_len=PROMPT)
    if name == "prefill_step":
        step = greedy_prefill(make_prefill_step(run)[0])
    else:
        step = greedy_decode(make_decode_step(run)[0])
    jaxpr = jax.make_jaxpr(step)(*_args(qwen_server, name)).jaxpr
    producer = {id(v): e for e in jaxpr.eqns for v in e.outvars}
    argmax, = [e for e in jaxpr.eqns if e.primitive.name == "argmax"]
    seen, todo = {}, list(argmax.invars)
    while todo:              # the argmax's inputs, back to the barrier
        eqn = producer.get(id(todo.pop()))
        if eqn is None or id(eqn) in seen:
            continue
        seen[id(eqn)] = eqn.primitive.name
        if eqn.primitive.name != "optimization_barrier":
            todo.extend(eqn.invars)
    assert "optimization_barrier" in seen.values()
    assert "dot_general" not in seen.values()
    # what lies between the barrier and the argmax only picks the last
    # position: no call into the model
    assert not any("jaxpr" in e.params for e in jaxpr.eqns
                   if id(e) in seen), sorted(seen.values())


def test_folded_decode_returns_token_next_position_and_cache():
    logits = jnp.zeros((BATCH, 1, 5)).at[0, 0, 3].set(1.0).at[1, 0, 1] \
        .set(2.0)

    def decode(params, cache, tokens, pos):
        return logits, cache + 1

    tok, pos, cache = jax.jit(greedy_decode(decode))(
        None, jnp.zeros(3), jnp.zeros((BATCH, 1), jnp.int32),
        jnp.full((BATCH, 1), 4, jnp.int32))
    assert tok.dtype == jnp.int32 and tok.shape == (BATCH, 1)
    np.testing.assert_array_equal(np.asarray(tok), [[3], [1]])
    np.testing.assert_array_equal(np.asarray(pos), [[5], [5]])
    np.testing.assert_array_equal(np.asarray(cache), np.ones(3))
