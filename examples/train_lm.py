"""End-to-end driver: train a ~100M-parameter qwen3-family model for a few
hundred steps on CPU with the full production stack — sharding rules,
AdamW, async checkpointing, crash-resume, straggler telemetry feeding the
paper's episode miner.

    PYTHONPATH=src python examples/train_lm.py [--steps 300] [--resume]
"""
import argparse
import dataclasses
import time

import jax

from repro.launch import compile_cache
from repro.configs import get_config
from repro.data import DataConfig, SyntheticCorpus
from repro.distributed.checkpoint import Checkpointer
from repro.distributed.fault_tolerance import StragglerMonitor, resilient_train_loop
from repro.distributed.sharding import MeshRules
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.optim import AdamW
from repro.train import make_train_step


def main():
    compile_cache.setup()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    # ~100M params: qwen3-0.6b config narrowed (vocab is most of 0.6B)
    cfg = dataclasses.replace(
        get_config("qwen3-0.6b"), name="qwen3-100m",
        n_layers=12, d_model=768, n_heads=12, n_kv_heads=4, head_dim=64,
        d_ff=2304, vocab=8192)
    mesh = make_mesh((1, jax.device_count()), ("data", "model"))
    rules = MeshRules(mesh)
    model = Model(cfg, constrain=rules.constrain, remat="none", mesh=mesh)
    opt = AdamW(lr=1e-3, warmup_steps=20, total_steps=args.steps)

    params = model.init(jax.random.PRNGKey(0))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"model: {cfg.name} ({n_params/1e6:.1f}M params), mesh {mesh.shape}")

    opt_state = opt.init(params)
    data = SyntheticCorpus(DataConfig(
        seq_len=args.seq_len, global_batch=args.batch, vocab=cfg.vocab,
        kind="markov"))
    step_fn_raw = jax.jit(make_train_step(model, opt), donate_argnums=(0, 1))

    def step_fn(state, batch):
        params, opt_state = state
        params, opt_state, metrics = step_fn_raw(params, opt_state, batch)
        return (params, opt_state), metrics

    ckpt = Checkpointer(args.ckpt_dir, keep=2)
    monitor = StragglerMonitor()
    losses = []

    def on_metrics(step, m):
        losses.append(m["loss"])
        if step % 25 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {m['loss']:.4f} "
                  f"lr {m['lr']:.2e} gnorm {m['grad_norm']:.2f}", flush=True)

    t0 = time.time()
    (params, opt_state), start, hist = resilient_train_loop(
        step_fn=step_fn, init_state=(params, opt_state),
        batch_iter=data.batches(), checkpointer=ckpt, n_steps=args.steps,
        ckpt_every=100, monitor=monitor, on_metrics=on_metrics,
        resume=args.resume)
    dt = time.time() - t0
    first, last = hist[0]["loss"], hist[-1]["loss"]
    print(f"\ntrained steps {start}..{args.steps} in {dt:.0f}s "
          f"({dt/max(1,len(hist)):.2f}s/step)")
    print(f"loss {first:.3f} -> {last:.3f}  (ckpts: {ckpt.list_steps()})")
    assert last < first - 0.3, "loss should decrease substantially"
    print("OK: loss decreased; checkpoint/resume available via --resume")


if __name__ == "__main__":
    main()
