"""Zamba2 on the normal serving path: a reduced zamba2-7b through
``ReplicatedServer.generate``, ``DecodeWorkload`` and ``FTSession``
replication, killed mid-answer and promoted, serves the tokens of a run
without a kill; the replica copy's span carries the bytes of the KV caches
and of the Mamba2 states it copies, and the obs registry has them as
gauges."""
import glob

import jax
import numpy as np
import pytest

from repro.ft.workload import state_bytes
from repro.launch.serve import ReplicatedServer

B, PROMPT, N_GEN, KILL_AT = 2, 24, 8, 3


@pytest.fixture(scope="module")
def server():
    return ReplicatedServer("zamba2-7b", batch=B, prompt_len=PROMPT, obs=True)


def _prompts(vocab):
    return np.random.default_rng(0).integers(0, vocab, (B, PROMPT),
                                             dtype=np.int32)


def _cache_sizes(srv):
    """Bytes of the reduced model's KV caches and recurrent states."""
    cfg = srv.cfg
    apps = len(cfg.hybrid_layers)
    slots = PROMPT + N_GEN
    kv = apps * (2 * B * slots * cfg.n_kv_heads * cfg.resolved_head_dim * 2
                 + B * slots * 4 + 4)
    d_inner = cfg.expand * cfg.d_model
    heads = d_inner // 64
    conv = d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    ssm = cfg.n_layers * B * (heads * 64 * cfg.ssm_state * 4
                              + (cfg.conv_kernel - 1) * conv * 2)
    return kv, ssm


def test_failover_serves_the_tokens_of_a_clean_run(server):
    prompts = _prompts(server.cfg.vocab_size)
    clean = server.generate(prompts, N_GEN)
    promotions = server.promotions
    faulty = server.generate(prompts, N_GEN, kill_at=KILL_AT)
    assert faulty.shape == (B, N_GEN)
    assert server.promotions == promotions + 1
    np.testing.assert_array_equal(faulty, clean)


def test_state_bytes_and_gauges(server):
    server.generate(_prompts(server.cfg.vocab_size), N_GEN)
    kv, ssm = _cache_sizes(server)
    gauges = server.obs.metrics.gauges
    assert gauges["serve.state_bytes.kv"] == kv
    assert gauges["serve.state_bytes.ssm"] == ssm
    assert state_bytes({"tok": np.zeros(3)}) == {"kv": 0, "ssm": 0}
    assert state_bytes(object()) == {"kv": 0, "ssm": 0}


def test_replica_copy_span_carries_the_state_bytes(server, tmp_path):
    from jax.profiler import ProfileData
    prompts = _prompts(server.cfg.vocab_size)
    with jax.profiler.trace(str(tmp_path)):
        server.generate(prompts, N_GEN, kill_at=KILL_AT)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    stats = [dict(e.stats) for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.ft.replica_copy")]
    kv, ssm = _cache_sizes(server)
    assert stats == [{"kv_bytes": kv, "ssm_bytes": ssm}]


def test_param_counts_match_the_published_model():
    """The registry's 18-layer cut has the parameters of ``transformers``'
    ``Zamba2ForCausalLM`` built (on the meta device) from the published
    keys in the cell's configuration file; the whole model has the
    7,356,749,648 the same build counts for all 81 layers."""
    import json
    from pathlib import Path
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from repro.configs import get_arch
    from repro.models import param_count
    path = Path(__file__).resolve().parents[1] / "bench" / "configs" / \
        "zamba2-7b-18l.json"
    published = json.loads(path.read_text())
    keys = {k: v for k, v in published.items()
            if k in transformers.Zamba2Config().to_dict()
            and k != "hybrid_layer_ids"}
    with torch.device("meta"):
        hf = transformers.Zamba2ForCausalLM(transformers.Zamba2Config(**keys))
    assert param_count(get_arch("zamba2-7b-18l")) == \
        sum(p.numel() for p in hf.parameters())
    assert param_count(get_arch("zamba2-7b")) == 7_356_749_648


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_named_scopes_mark_the_parts_of_the_programs(server, step):
    """The mixer, the shared block, the adapter and the linear carry their
    ``jax.named_scope`` names into the served programs' op metadata."""
    prompts = _prompts(server.cfg.vocab_size)
    batch = server.workload(prompts).batch
    if step == "prefill":
        lowered = server.prefill.lower(server.params, batch, N_GEN)
    else:
        _, _, cache = jax.eval_shape(server.prefill, server.params, batch,
                                     N_GEN)
        tok = jax.ShapeDtypeStruct((B, 1), jax.numpy.int32)
        lowered = server.decode.lower(server.params, cache, tok, tok)
    text = lowered.compile().as_text()
    for scope in ("zamba2.mamba", "zamba2.shared_block", "zamba2.adapter",
                  "zamba2.linear"):
        assert scope in text, scope
