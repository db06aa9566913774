"""The serving runtime (repro/serve): paged-cache equivalence, continuous
batching, admission control, and the compile cache.

The load-bearing invariants (DESIGN.md §Serving):

  * paged decode is BIT-IDENTICAL to the dense-cache oracle — including
    after blocks retire and get reused by later requests;
  * continuous batching never changes any request's token stream: batched
    output == serving the same requests one at a time == chunk-size
    invariant;
  * windowed (ring-buffer) layers match a full-recompute greedy oracle
    even after the ring wraps;
  * admission is conservative: a tight pool defers requests instead of
    corrupting live lanes, and an impossible request fails loudly;
  * tensor-parallel decode is pinned against single-device in a forced
    multi-device subprocess (slow lane).
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import obs
from repro.config import get_arch
from repro.serve import (BlockAllocator, Request, ServeEngine, check_arch,
                         prompt_tokens, run_host_loop, serve_trace,
                         synthetic_trace)

pytestmark = pytest.mark.slow  # jitted serving programs — compile-heavy


@pytest.fixture(scope="module")
def setup():
    """One reduced arch + params shared by every engine in this module
    (build_programs memoizes per (cfg, geo), so same-shape engines also
    share executables)."""
    import jax
    from repro.models import model

    cfg = get_arch("qwen2-7b").reduced()
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _ids(rep):
    return {r.rid: tuple(r.tokens) for r in rep.results}


TRACE = dict(pattern="uniform", prompt_len=12, max_new=6, gap=2,
             vary_new=True, seed=3)
ENGINE = dict(width=3, block_size=4, max_seq_len=32, chunk_buckets=(4, 8))


def test_paged_matches_dense_bitwise_with_block_reuse(setup):
    """The tentpole invariant: greedy ids from the paged cache equal the
    dense oracle bit-for-bit, on a trace whose retirements force block
    reuse (LIFO allocator hands freed blocks to later requests)."""
    cfg, params = setup
    trace = synthetic_trace(6, **TRACE)
    paged = serve_trace(cfg, trace, params=params, kv_cache="paged",
                        **ENGINE)
    dense = serve_trace(cfg, trace, params=params, kv_cache="dense",
                        **ENGINE)
    assert paged.blocks_reused > 0, "trace never exercised block reuse"
    assert _ids(paged) == _ids(dense)


def test_batched_equals_sequential(setup):
    """Continuous batching is invisible per request: the same ids come out
    of a width-3 batch and of serving each request alone."""
    cfg, params = setup
    trace = synthetic_trace(6, **TRACE)
    batched = _ids(serve_trace(cfg, trace, params=params, **ENGINE))
    for r in trace:
        alone = serve_trace(cfg, [dataclasses.replace(r, arrival=0)],
                            params=params, **ENGINE)
        assert _ids(alone)[r.rid] == batched[r.rid], f"rid {r.rid}"


def test_chunk_bucket_invariance(setup):
    """Prefill chunking is a launch-shape choice, not a numeric one."""
    cfg, params = setup
    trace = synthetic_trace(3, pattern="burst", prompt_len=11, max_new=4)
    base = None
    for buckets in ((16,), (4, 8), (2,)):
        rep = serve_trace(cfg, trace, params=params,
                          **{**ENGINE, "chunk_buckets": buckets})
        if base is None:
            base = _ids(rep)
        else:
            assert _ids(rep) == base, f"buckets {buckets}"


def test_engine_matches_legacy_host_loop(setup):
    """Old path and new path serve the same tokens (same greedy ids),
    which is what makes the BENCH_serve twin rows comparable."""
    cfg, params = setup
    trace = synthetic_trace(4, pattern="burst", prompt_len=12, max_new=5)
    eng = serve_trace(cfg, trace, params=params, **ENGINE)
    legacy = run_host_loop(cfg, trace, params=params, width=2)
    assert _ids(eng) == _ids(legacy)


def test_ring_window_covers_full_context_bitwise():
    """A window >= total length makes the ring a plain cache: bit-equal
    ids to the same arch with windowing off."""
    import jax
    from repro.models import model

    base = get_arch("starcoder2-15b").reduced()
    win = dataclasses.replace(base, sliding_window=32)
    full = dataclasses.replace(base, sliding_window=None)
    params = model.init_params(full, jax.random.PRNGKey(1))
    trace = synthetic_trace(2, pattern="burst", prompt_len=10, max_new=5)
    kw = dict(width=2, block_size=4, max_seq_len=20, chunk_buckets=(4,))
    a = serve_trace(win, trace, params=params, **kw)
    b = serve_trace(full, trace, params=params, **kw)
    assert _ids(a) == _ids(b)


def test_ring_wraparound_matches_recompute_oracle():
    """After the ring wraps (len > window), decode must equal a greedy
    oracle that recomputes the full forward each step (windowed attention
    applied functionally, no ring state)."""
    import jax
    import jax.numpy as jnp
    from repro.models import model

    base = get_arch("starcoder2-15b").reduced()
    cfg = dataclasses.replace(base, sliding_window=12)
    params = model.init_params(cfg, jax.random.PRNGKey(2))
    prompt_len, max_new = 20, 8          # wraps: 20+8 > window 12
    trace = synthetic_trace(1, pattern="burst", prompt_len=prompt_len,
                            max_new=max_new, seed=5)
    rep = serve_trace(cfg, trace, params=params, width=1, block_size=4,
                      max_seq_len=32, chunk_buckets=(8,))
    got = list(_ids(rep)[0])

    toks = list(np.asarray(prompt_tokens(trace[0], cfg.vocab_size)))
    oracle = []
    fwd = jax.jit(lambda p, t: model.forward(p, cfg, {"tokens": t})[0])
    for _ in range(max_new):
        logits = fwd(params, jnp.asarray([toks], jnp.int32))
        nxt = int(np.argmax(np.asarray(logits[0, -1])))
        oracle.append(nxt)
        toks.append(nxt)
    assert got == oracle


def test_tight_pool_defers_admission(setup):
    """A pool sized for ~1.5 sequences forces the scheduler to queue: all
    requests still finish with correct ids, but admission is staggered
    even under burst arrivals."""
    cfg, params = setup
    trace = synthetic_trace(4, pattern="burst", prompt_len=12, max_new=6)
    roomy = serve_trace(cfg, trace, params=params, **ENGINE)
    # blocks_for(18) = 5 → 7 free blocks fit one sequence + change
    tight = serve_trace(cfg, trace, params=params,
                        **{**ENGINE, "num_blocks": 8})
    assert _ids(tight) == _ids(roomy)
    admits = sorted(r.admit_step for r in tight.results)
    assert admits[0] < admits[-1], "tight pool never deferred admission"


def test_impossible_request_raises(setup):
    cfg, params = setup
    eng = ServeEngine(cfg, params, **{**ENGINE, "num_blocks": 3})
    with pytest.raises(RuntimeError, match="can ever free up"):
        eng.run([Request(rid=0, arrival=0, prompt_len=12, max_new=6)])
    too_long = [Request(rid=0, arrival=0, prompt_len=30, max_new=8)]
    with pytest.raises(ValueError, match="exceeds max servable"):
        ServeEngine(cfg, params, **ENGINE).run(too_long)


def test_engine_rejects_unsupported_archs(setup):
    cfg, params = setup
    ssm = get_arch("mamba2-130m").reduced()
    with pytest.raises(ValueError, match="attention-family"):
        check_arch(ssm)
    with pytest.raises(ValueError):
        ServeEngine(ssm)
    # legacy fallback also refuses ragged prompts (lockstep batching)
    ragged = [Request(rid=0, arrival=0, prompt_len=8, max_new=2),
              Request(rid=1, arrival=0, prompt_len=9, max_new=2)]
    with pytest.raises(ValueError, match="prompt_len"):
        run_host_loop(cfg, ragged, params=params)


def test_serve_run_emits_valid_telemetry(setup, tmp_path):
    """A served trace under obs.recording() produces a schema-valid
    record file containing the serve spans and admit/retire events (the
    CI serve-smoke step validates the same thing via the obs CLI)."""
    from repro.obs import schema

    cfg, params = setup
    trace = synthetic_trace(2, pattern="burst", prompt_len=8, max_new=3)
    path = str(tmp_path / "serve.jsonl")
    with obs.recording(path):
        serve_trace(cfg, trace, params=params, **ENGINE)
    assert schema.validate_file(path) > 0
    kinds = [json.loads(l) for l in open(path)]
    names = {r.get("name") for r in kinds}
    assert {"serve/run", "serve/prefill"} <= names
    events = {r["name"] for r in kinds if r["kind"] == "event"}
    assert {"serve_admit", "serve_retire", "serve_report"} <= events


# -- allocator unit tests (no jax) ----------------------------------------

def test_allocator_lifo_reuse_and_reservations():
    a = BlockAllocator(6)                  # usable ids 1..5
    a.reserve(0, 3)
    assert a.available() == 2
    got = [a.alloc(0) for _ in range(3)]
    assert got == [1, 2, 3]                # deterministic order
    assert a.in_use == 3 and a.reuse_count == 0
    a.release(0, got)
    assert a.available() == 5
    a.reserve(1, 1)
    assert a.alloc(1) == 3                 # LIFO: last freed, first out
    assert a.reuse_count == 1


def test_allocator_guards():
    a = BlockAllocator(4)
    with pytest.raises(RuntimeError, match="exceeds available"):
        a.reserve(0, 4)
    with pytest.raises(RuntimeError, match="without reservation"):
        a.alloc(0)
    a.reserve(0, 2)
    with pytest.raises(RuntimeError, match="exceeds available"):
        a.reserve(1, 2)                    # only 1 unreserved left
    with pytest.raises(ValueError, match="bad block id"):
        a.release(0, [0])                  # trash block is unreleasable


def test_compile_cache_env_and_flag(tmp_path, monkeypatch):
    """The cache lives at $JAX_COMPILATION_CACHE_DIR when it is set, else
    at the fixed ``<repo>/.jax_cache``; a compile under the enabled cache
    writes an entry there."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch import compile_cache as cc

    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.cache_dir() == os.path.join(root, ".jax_cache")

    d = tmp_path / "cc"
    monkeypatch.setenv(cc.ENV_VAR, str(d))
    assert cc.cache_dir() == str(d)
    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_compilation_cache_include_metadata_in_key")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        assert cc.enable_compile_cache() == str(d)
        assert jax.config.jax_compilation_cache_dir == str(d)
        compilation_cache.reset_cache()
        jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(5.0)).block_until_ready()
        assert os.listdir(d), "no cache entry written"
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


# -- tensor-parallel decode (subprocess: forced 2 host devices) -----------

TP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import sys, json
    sys.path.insert(0, "src")
    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.config import get_arch
    from repro.launch.mesh import make_test_mesh
    from repro.models import model
    from repro.serve import serve_trace, synthetic_trace

    cfg = get_arch("qwen2-7b").reduced()
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    trace = synthetic_trace(3, pattern="uniform", prompt_len=12, max_new=5,
                            gap=2, seed=4)
    kw = dict(width=2, block_size=4, max_seq_len=20, chunk_buckets=(4, 8))
    single = serve_trace(cfg, trace, params=params, **kw)
    tp = serve_trace(cfg, trace, params=params,
                     mesh=make_test_mesh(model_axis=2), **kw)
    out = {"single": {r.rid: r.tokens for r in single.results},
           "tp": {r.rid: r.tokens for r in tp.results}}
    print("RESULT" + json.dumps(out))
""")


def test_tp_decode_matches_single_device():
    proc = subprocess.run([sys.executable, "-c", TP_SCRIPT],
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT")][0]
    out = json.loads(line[len("RESULT"):])
    assert out["tp"] == out["single"]
    assert out["tp"], "empty results"
