#!/usr/bin/env python
"""Ad-hoc decode perf probe on the live chip (dev tool, not bench.py).

Usage: python scripts/measure_decode.py [model] [batch] [quant] [chunk] [ctx]
"""
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import numpy as np

from llmq_tpu.engine.executor import JaxExecutor
from llmq_tpu.engine.kv_allocator import PageAllocator
from llmq_tpu.models.llama import (get_config, init_params,
                                   init_params_quantized, param_count)

model = sys.argv[1] if len(sys.argv) > 1 else "llama3-1b"
batch = int(sys.argv[2]) if len(sys.argv) > 2 else 64
quant = (sys.argv[3] if len(sys.argv) > 3 else "int8") == "int8"
chunk = int(sys.argv[4]) if len(sys.argv) > 4 else 64
max_seq = int(sys.argv[5]) if len(sys.argv) > 5 else 1024
page_size_arg = int(sys.argv[6]) if len(sys.argv) > 6 else 16

dev = jax.devices()[0]
print(f"device={dev.device_kind} model={model} B={batch} quant={quant} "
      f"chunk={chunk} ctx={max_seq}", flush=True)

cfg = get_config(model, max_seq_len=max_seq)
t0 = time.perf_counter()
if quant:
    params = init_params_quantized(jax.random.PRNGKey(0), cfg)
else:
    params = init_params(jax.random.PRNGKey(0), cfg)
jax.block_until_ready(params)
print(f"init {time.perf_counter()-t0:.1f}s, {param_count(params)/1e9:.2f}B leaves", flush=True)

page_size = page_size_arg
pages_per_seq = max_seq // page_size
num_pages = batch * pages_per_seq + 1
kv_quant = os.environ.get("LLMQ_KV_QUANT", "") == "int8"
import jax.numpy as jnp
ex = JaxExecutor(cfg, params, batch_size=batch, page_size=page_size,
                 num_pages=num_pages, chunk_size=chunk,
                 prefill_buckets=[128, 512], eos_id=-1,
                 cache_dtype=(jnp.int8 if kv_quant else None))
print(f"kv cache: {'int8' if kv_quant else 'model dtype'}", flush=True)
t0 = time.perf_counter()
ex.warmup()
print(f"warmup {time.perf_counter()-t0:.1f}s", flush=True)

rng = np.random.default_rng(0)
bt = np.zeros((batch, ex.spec.max_pages_per_seq), np.int32)
alloc = PageAllocator(num_pages, page_size)
for b in range(batch):
    bt[b, :pages_per_seq] = alloc.alloc(pages_per_seq)
prompt_len = 128
toks = rng.integers(10, cfg.vocab_size - 10,
                    size=(batch, prompt_len)).astype(np.int32)
for b in range(batch):
    ex.prefill(list(toks[b]), 0, bt[b], 0.0, b)

# prefill timing (bucket 512)
pf = rng.integers(10, cfg.vocab_size - 10, size=512).astype(np.int32)
t0 = time.perf_counter()
tok = None
for _ in range(4):
    tok = ex.prefill_async(list(pf), prompt_len, bt[0], 0.0)
_ = np.asarray(tok)
pf_tps = 4 * 512 / (time.perf_counter() - t0)

positions = np.full(batch, prompt_len, np.int32)
tokens = toks[:, -1].copy()
temps = np.zeros(batch, np.float32)
budgets = np.full(batch, chunk, np.int32)
# Chained device-resident carry (the engine's pipelined path): one host
# fetch at the end — per-call fetches would bill the host round-trip
# to the device step.
h = ex.decode_chunk_start(tokens, positions, bt, temps, budgets)
h.fetch()
n_calls = max(1, min(512 // chunk, (max_seq - prompt_len) // chunk - 1))
t0 = time.perf_counter()
for _ in range(n_calls):
    h = ex.decode_chunk_start(None, None, bt, temps, budgets, carry=h)
h.fetch()
dt = time.perf_counter() - t0
n_tok = n_calls * chunk
step_ms = dt / n_tok * 1e3
print(f"decode: {step_ms:.2f} ms/step  {batch*n_tok/dt:,.0f} tok/s  "
      f"(calls={n_calls})  prefill_pipelined={pf_tps:,.0f} tok/s", flush=True)
