"""What the engine puts into a ``jax.profiler`` trace: every compiled
program named after its cache key, the model's ops under named scopes,
and each step's host phases as spans on the profiler's clock."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.model import transformer as T
from repro.serve import PagedEngine, PagedServeConfig
from repro.serve.telemetry import program_name

from _helpers import tiny


def _engine():
    cfg = tiny(n_layers=2)
    ms = T.build_structure(cfg, tp=1)
    params = T.init_params(ms, jax.random.PRNGKey(0))
    psv = PagedServeConfig(n_slots=2, page_size=8, n_pages=9, max_len=32,
                           cache_dtype=jnp.float32, prefix_cache=True)
    return cfg, PagedEngine(params, ms, psv)


def test_program_name_is_the_cache_key():
    assert program_name("main", "decode", 12) == "decode_main_12"
    assert program_name("main", "prefill_bucket", (512, 8)) == \
        "prefill_bucket_main_512x8"
    assert program_name("spec_verify", "rewind", 16) == \
        "rewind_spec_verify_16"


def test_programs_carry_their_names_and_scopes():
    cfg, eng = _engine()
    eng.add_request(np.arange(5) % cfg.vocab_size, 3)
    eng.step()
    key = jax.random.PRNGKey(1)
    dec = eng._decode_fn("main").lower(
        eng.params, eng.caches, jnp.asarray(eng.tok), jnp.asarray(eng.pos),
        jnp.asarray(eng.block_tables), jnp.zeros((2,), bool), key)
    rows = 2
    ctx = eng._bucket_ctx_pages("main")
    pre = eng._bucket_prefill_fn(8, rows, "main").lower(
        eng.params, eng.caches, jnp.zeros((rows, 8), jnp.int32),
        jnp.ones((rows,), jnp.int32), jnp.zeros((rows, 1), jnp.int32),
        jnp.zeros((rows, ctx), jnp.int32), jnp.zeros((rows,), jnp.int32),
        key)
    want = {"decode": ("jit_decode_main_2", dec),
            "prefill": ("jit_prefill_bucket_main_8x2", pre)}
    for module, lowered in want.values():
        text = lowered.compile().as_text()
        assert text.startswith(f"HloModule {module},"), text[:80]
        for scope in ("/layer_scan/", "/attn/", "/attn_core/", "/mlp/",
                      "/head/", "/sample/", "/kv_write/"):
            assert scope in text, (module, scope)
    assert "/kv_gather/" in want["prefill"][1].compile().as_text()
    # The decode scan's body keeps the scopes too.
    assert "/while/body/" in dec.compile().as_text()


def test_step_spans_nest_under_engine_step(tmp_path):
    cfg, eng = _engine()
    eng.add_request(np.arange(6) % cfg.vocab_size, 4)
    eng.step()                      # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    eng.step()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
             for p in pd.planes if p.name.startswith("/host:")
             for ln in p.lines for e in ln.events
             if e.name.startswith("engine.")]
    steps = [s for s in spans if s[2] == "engine.step"]
    assert len(steps) == 1 and steps[0][3] == {"step": 1}
    s0, s1 = steps[0][:2]
    inside = {n for a, b, n, _ in spans if s0 <= a and b <= s1}
    assert {"engine.admit", "engine.decode", "engine.readback",
            "engine.bookkeep"} <= inside
    dec = [d for _, _, n, d in spans if n == "engine.decode"]
    assert dec == [{"cohort": "main"}]
