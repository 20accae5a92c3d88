"""graftquant smoke: int8 KV + quantized transfer end-to-end on CPU.

The contract, asserted in one short run (same body runs in tier-1 —
``tests/test_graftquant.py::test_quant_smoke_end_to_end``):

1. **Transcript equality**: the int8-KV engine's greedy streams
   (dense AND paged) are byte-identical to the model-dtype engine's
   at this geometry — measured, never assumed (int8 KV is not
   token-exact by construction; the full pinned matrix incl. spec
   decode and the socket fleet lives in ``tests/test_graftquant.py``).
2. **The residency claim**: ``per_slot_kv_bytes`` is THE shape x
   dtype product the quantized pool allocates (planner == allocator
   byte-for-byte at a live ledger), and at head_dim=64 — gpt_small's
   geometry — the per-slot KV ratio clears **1.8x** for bf16 caches
   and ~3.8x for f32, so a fixed budget holds >= 1.8x the requests.
3. **The quality audit**: the max-abs teacher-forced logit delta
   between the two cache representations is NONZERO (the pin is a
   real measurement, not a no-op) and inside the committed 5e-3.
4. **Quantized transfer**: a detached prefill leaves the wire seam
   already int8 + f32 scales at < 0.6x the model-dtype payload, and
   splices into a second quantized engine transcript-equal.

Run: ``make quant`` (or ``python benchmarks/quant_smoke.py``).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_smoke():
    import jax.numpy as jnp
    import numpy as np

    from pytorch_multiprocessing_distributed_tpu import models
    from pytorch_multiprocessing_distributed_tpu.analysis.meter import (
        plan_capacity)
    from pytorch_multiprocessing_distributed_tpu.inference import (
        teacher_forced_logits)
    from pytorch_multiprocessing_distributed_tpu.runtime import (
        hbm as hbm_ledger)
    from pytorch_multiprocessing_distributed_tpu.serving import (
        PagePool, ServingEngine, init_params)
    from pytorch_multiprocessing_distributed_tpu.serving.scheduler import (
        Request)

    model = models.GPT(vocab_size=61, max_seq_len=64, hidden_size=128,
                       num_layers=2, num_heads=2, mlp_dim=64,
                       attn_impl="xla")  # head_dim=64, gpt_small's
    params = init_params(model, 1)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 61, (n,)).tolist() for n in (3, 12, 7)]
    s_max = 32

    # ---- 1: transcript equality, int8 pages at worst-case capacity
    # and under it
    ref_eng = ServingEngine(model, params, max_slots=2, s_max=s_max,
                            min_bucket=8)
    ref = ref_eng.serve([(p, 6) for p in prompts])
    for tag, kw in (("parity", {}),
                    ("paged", {"page_size": 8, "num_pages": 9})):
        eng = ServingEngine(model, params, max_slots=2, s_max=s_max,
                            min_bucket=8, kv_dtype="int8", **kw)
        got = eng.serve([(p, 6) for p in prompts])
        for a, b, p in zip(got, ref, prompts):
            assert a.tokens == b.tokens, (
                f"int8 {tag} stream diverged (prompt len {len(p)}): "
                f"{a.tokens} vs {b.tokens}")
    print("quant smoke: int8 parity + paged transcripts byte-equal vs "
          "model-dtype engine OK")

    # ---- 2: the residency claim, byte-exact at a live ledger
    kv_model = PagePool.per_slot_kv_bytes(model, s_max)
    kv_int8 = PagePool.per_slot_kv_bytes(model, s_max, "int8")
    with hbm_ledger.scoped_ledger() as ledger:
        pool = PagePool(model, 4, s_max, page_size=8, kv_dtype="int8")
        kv_entry = ledger.entries()["serving.kv_pages"]
    # every slot's worst case plus the scratch page
    assert kv_entry[1] == 4 * kv_int8 + pool.page_bytes, (
        "quantized PagePool bytes diverge from per_slot_kv_bytes")
    del pool
    # bf16 twin of the same geometry: the TPU headline ratio (byte
    # math only — per_slot_kv_bytes reads geometry, no allocation)
    bf16 = models.GPT(vocab_size=61, max_seq_len=64, hidden_size=128,
                      num_layers=2, num_heads=2, mlp_dim=64,
                      attn_impl="xla", dtype=jnp.bfloat16)
    r_bf16 = (PagePool.per_slot_kv_bytes(bf16, s_max)
              / PagePool.per_slot_kv_bytes(bf16, s_max, "int8"))
    r_f32 = kv_model / kv_int8
    assert r_bf16 >= 1.8, f"bf16 head_dim=64 ratio {r_bf16:.3f} < 1.8"
    assert r_f32 >= 3.5, f"f32 head_dim=64 ratio {r_f32:.3f} < 3.5"
    budget = 1 << 24
    dense_plan = plan_capacity(model, s_max, budget)
    quant_plan = plan_capacity(model, s_max, budget, kv_dtype="int8")
    assert quant_plan["max_slots"] >= 1.8 * dense_plan["max_slots"]
    print(f"quant smoke: KV/slot {kv_model} -> {kv_int8} B "
          f"(f32 {r_f32:.2f}x, bf16 {r_bf16:.2f}x), planner "
          f"{dense_plan['max_slots']} -> {quant_plan['max_slots']} "
          f"slots at a fixed budget OK")

    # ---- 3: quality audit — nonzero, bounded logit delta
    full = jnp.asarray(list(prompts[1]) + list(ref[1].tokens))[None, :]
    lg_ref = teacher_forced_logits(model, params, full,
                                   len(prompts[1]))
    lg_q = teacher_forced_logits(model, params, full, len(prompts[1]),
                                 kv_dtype="int8")
    delta = float(jnp.max(jnp.abs(lg_q - lg_ref)))
    assert 0.0 < delta < 5e-3, (
        f"teacher-forced logit delta {delta:.2e} outside (0, 5e-3)")
    print(f"quant smoke: max |logit delta| = {delta:.2e} "
          f"(nonzero, < 5e-3) OK")

    # ---- 4: quantized transfer — halved payload, transcript-equal
    sender = ServingEngine(model, params, max_slots=3, s_max=s_max,
                           min_bucket=8, kv_dtype="int8")
    recv = ServingEngine(model, params, max_slots=3, s_max=s_max,
                         min_bucket=8, kv_dtype="int8")
    reqs = [Request(p, 6, None) for p in prompts]
    for r in reqs:
        tok0, kb, vb, ks, vs = sender.prefill_detached_wire(r)
        assert kb.dtype == np.int8 and ks.dtype == np.float32
        full_bytes = kb.size * np.dtype(model.dtype).itemsize
        assert kb.nbytes + ks.nbytes < 0.6 * full_bytes, (
            "quantized transfer payload is not < 0.6x model-dtype")
        recv.admit_prefilled(r, tok0, kb, vb, k_scale=ks, v_scale=vs)
    list(recv.run())
    for r, b in zip(reqs, ref):
        assert list(r.tokens) == list(b.tokens), (
            "spliced quantized stream diverged")
    print("quant smoke: quantized PageTransfer < 0.6x payload, "
          "spliced streams transcript-equal OK")


if __name__ == "__main__":
    run_smoke()
    print("quant smoke OK")
