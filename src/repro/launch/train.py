"""Production-style training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke \
        --steps 50 --batch 8 --seq 256 --ckpt-dir /tmp/repro_ckpt

Wires the full stack: arch registry -> sharding plan over the host mesh ->
elastic trainer (checkpoint/auto-resume, membership events) -> deterministic
data pipeline. ``--simulate-failure STEP:NEW_HOSTS`` exercises the elastic
re-mesh path mid-run (single-host container: hosts = simulated DP groups).

On a real cluster the same module runs under ``jax.distributed`` with the
production mesh from ``repro.launch.mesh``.
"""

from __future__ import annotations

import argparse

import jax

from repro.checkpoint import CheckpointManager
from repro.configs import ARCH_NAMES, get_config
from repro.data import DataConfig, SyntheticLM
from repro.launch.mesh import make_host_mesh, make_mesh
from repro.models import ForwardOptions, init_encdec_params, init_lm_params
from repro.train.elastic import ElasticConfig, ElasticTrainer
from repro.train.optimizer import AdamW, cosine_schedule


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="granite-8b", choices=ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument(
        "--simulate-failure", default=None,
        help="STEP:NEW_HOSTS — elastic re-mesh before STEP",
    )
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    if cfg.is_encoder_decoder:
        raise SystemExit("training launcher drives LM archs; whisper uses "
                         "the encdec loss path in tests/benchmarks")

    data = SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch,
    ))
    optimizer = AdamW(schedule=cosine_schedule(args.lr, 10, args.steps))
    ckpt = CheckpointManager(args.ckpt_dir, keep=3)

    def make_mesh_fn(n_hosts: int):
        # host-count -> dp width at smoke scale
        n_dev = len(jax.devices())
        dp = max(min(n_hosts, n_dev), 1)
        return make_mesh(1, dp, max(n_dev // dp, 1))

    trainer = ElasticTrainer(
        cfg=cfg,
        optimizer=optimizer,
        data=data,
        ckpt=ckpt,
        make_mesh_fn=make_mesh_fn,
        opts=ForwardOptions(attn_impl="reference"),
        elastic_cfg=ElasticConfig(checkpoint_every=args.ckpt_every),
    )
    trainer.start(
        n_hosts=1,
        init_params_fn=lambda: init_lm_params(cfg, jax.random.PRNGKey(0))[0],
    )

    events = {}
    if args.simulate_failure:
        step_s, hosts_s = args.simulate_failure.split(":")
        events[int(step_s)] = int(hosts_s)

    history = trainer.run(args.steps, membership_events=events)
    for h in history[:: max(len(history) // 10, 1)]:
        print(f"step {h['step']:4d} loss={h['loss']:.4f} nll={h['nll']:.4f}")
    print(f"final loss={history[-1]['loss']:.4f}; checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
