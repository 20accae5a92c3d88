"""LM convergence parity: this framework vs transformers, SAME GPT-2
init, SAME byte-corpus batches (VERDICT r4 #3, LM record).

The image-side counterpart is ``convergence.py``; here the model is a
GPT-2 (built by ``transformers.GPT2LMHeadModel``, imported into the
framework via ``utils.gpt_interop.from_gpt2_state_dict`` — the exact
``--hf_init`` CLI path) and the data is a deterministic byte-level
corpus streamed by the framework's own ``TokenLoader`` on BOTH sides.
Objective on both sides: exact mean next-token CE over positions with
a successor (``train.lm._next_token_targets`` semantics), plain SGD
with identical hyperparameters — any trajectory gap is framework
semantics, nothing else.

Writes ``benchmarks/lm_convergence_record.json`` and prints a one-line
JSON summary (headline: final-epoch mean-loss delta; step-0 loss delta
pins the imported-init forward parity).

Run: ``python benchmarks/lm_convergence.py [--epochs 3]``
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import benchmarks._common as _common  # noqa: E402

RECORD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "lm_convergence_record.json")

# GPT-2 small-geometry test double (matches tests/test_lm_cli.py):
# byte-level 257 vocab, 4 layers, 128 wide, 4 heads, no dropout
GPT2_KW = dict(vocab_size=257, n_positions=256, n_embd=128, n_layer=4,
               n_head=4, resid_pdrop=0.0, embd_pdrop=0.0,
               attn_pdrop=0.0, tie_word_embeddings=False)
LR = 0.1


def make_corpus(args):
    from pytorch_multiprocessing_distributed_tpu.data.text import tokenize

    text = ("the quick brown fox jumps over the lazy dog. "
            "pack my box with five dozen liquor jugs! "
            "how vexingly quick daft zebras jump? ") * args.repeats
    return tokenize(text)


def make_loader(args, tokens):
    from pytorch_multiprocessing_distributed_tpu.data.lm import TokenLoader

    return TokenLoader(tokens, batch_size=args.batch_size,
                       seq_len=args.seq_len, world_size=1, seed=0)


def run_framework(args, sd, tokens):
    import jax
    import jax.numpy as jnp

    from pytorch_multiprocessing_distributed_tpu.parallel import make_mesh
    from pytorch_multiprocessing_distributed_tpu.train.lm import (
        create_lm_train_state, make_lm_train_step)
    from pytorch_multiprocessing_distributed_tpu.train.optim import sgd
    from pytorch_multiprocessing_distributed_tpu.utils.gpt_interop import (
        from_gpt2_state_dict)

    model, params = from_gpt2_state_dict(sd, num_heads=GPT2_KW["n_head"],
                                         attn_impl="xla")
    mesh = make_mesh(1, devices=jax.devices()[:1])
    opt = sgd(learning_rate=LR, momentum=0.9, weight_decay=0.0,
              nesterov=False)
    state = create_lm_train_state(
        model, jax.random.PRNGKey(0),
        jnp.zeros((2, args.seq_len), jnp.int32), opt)
    state = state.replace(params=jax.tree.map(jnp.asarray, params))
    step = make_lm_train_step(model, opt, mesh)

    loader = make_loader(args, tokens)
    losses = []
    for epoch in range(1, args.epochs + 1):
        state = state.replace(epoch=jnp.asarray(epoch, jnp.int32))
        loader.set_epoch(epoch)
        ep = []
        for batch in loader:
            tok = jax.device_put(jnp.asarray(batch))
            state, metrics = step(state, tok)
            ep.append(float(np.asarray(metrics["loss"])))
        losses.append(ep)
        print(f"[framework] epoch {epoch}: loss {np.mean(ep):.4f}",
              file=sys.stderr, flush=True)
    return losses


def run_torch(args, sd, tokens):
    import torch
    import torch.nn.functional as F
    import transformers

    model = transformers.GPT2LMHeadModel(
        transformers.GPT2Config(**GPT2_KW))
    model.load_state_dict(sd)
    model.train()
    optimizer = torch.optim.SGD(model.parameters(), lr=LR, momentum=0.9)

    loader = make_loader(args, tokens)
    losses = []
    for epoch in range(1, args.epochs + 1):
        loader.set_epoch(epoch)
        ep = []
        for batch in loader:
            x = torch.from_numpy(np.ascontiguousarray(batch)).long()
            logits = model(x).logits
            # exact _next_token_targets semantics: position j predicts
            # token j+1; the final position has no successor
            loss = F.cross_entropy(
                logits[:, :-1].reshape(-1, logits.shape[-1]),
                x[:, 1:].reshape(-1))
            optimizer.zero_grad()
            loss.backward()
            optimizer.step()
            ep.append(float(loss.detach()))
        losses.append(ep)
        print(f"[torch]     epoch {epoch}: loss {np.mean(ep):.4f}",
              file=sys.stderr, flush=True)
    return losses


def main():
    _common.enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", default=3, type=int)
    p.add_argument("--batch_size", default=8, type=int)
    p.add_argument("--seq_len", default=64, type=int)
    p.add_argument("--repeats", default=120, type=int,
                   help="corpus length knob (~125 bytes per repeat)")
    args = p.parse_args()

    import jax
    import torch
    import transformers

    platform = jax.devices()[0].platform
    torch.manual_seed(0)
    src = transformers.GPT2LMHeadModel(
        transformers.GPT2Config(**GPT2_KW))
    sd = src.state_dict()

    tokens = make_corpus(args)
    t0 = time.time()
    fw = run_framework(args, sd, tokens)
    fw_s = time.time() - t0
    t0 = time.time()
    th = run_torch(args, sd, tokens)
    th_s = time.time() - t0

    fw_ep = [float(np.mean(e)) for e in fw]
    th_ep = [float(np.mean(e)) for e in th]
    record = {
        "platform": platform,
        "model": "GPT2LMHeadModel " + json.dumps(GPT2_KW),
        "optimizer": f"SGD lr={LR} momentum=0.9 (both sides)",
        "epochs": args.epochs,
        "batch_size": args.batch_size,
        "seq_len": args.seq_len,
        "corpus_tokens": int(len(tokens)),
        "identical_init": True,
        "identical_batches": True,
        "framework": {"epoch_loss": fw_ep, "seconds": round(fw_s, 1)},
        "torch_cpu": {"epoch_loss": th_ep, "seconds": round(th_s, 1)},
        # step-0 pins the imported-init forward+loss; the final epoch
        # pins where both optimizers converge to
        "step0_loss_delta": round(fw[0][0] - th[0][0], 6),
        "final_loss_delta": round(fw_ep[-1] - th_ep[-1], 6),
    }
    with open(RECORD, "w") as f:
        json.dump(record, f, indent=2)
    print(json.dumps({
        "metric": "gpt2_lm_convergence_final_loss_delta_vs_torch",
        "value": record["final_loss_delta"],
        "unit": "nats",
        "extra": {k: record[k] for k in
                  ("platform", "epochs", "corpus_tokens",
                   "step0_loss_delta")},
    }))


if __name__ == "__main__":
    main()
