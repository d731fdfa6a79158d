"""Train an embedding-tower LM (reduced config) with the fault-tolerant
loop: checkpoints, a simulated mid-run failure, and resume.

    PYTHONPATH=src python examples/train_embedder.py [--arch qwen2-7b]
"""
import argparse
import shutil

from repro.configs.base import get_arch
from repro.train.loop import TrainConfig, train
from repro.launch.entry import start


def main():
    start()  # compile cache + platform check
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-moe-1b-a400m")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--ckpt", default="/tmp/repro_train_example")
    args = ap.parse_args()

    shutil.rmtree(args.ckpt, ignore_errors=True)
    cfg = get_arch(args.arch).reduced()
    tcfg = TrainConfig(steps=args.steps, batch=8, seq_len=128,
                       ckpt_dir=args.ckpt, ckpt_every=10, peak_lr=1e-3)

    tripped = {"done": False}

    def chaos(step):  # one injected node failure mid-run
        if step == args.steps // 2 and not tripped["done"]:
            tripped["done"] = True
            print(f"!! injecting node failure at step {step} "
                  f"(loop will restore the latest checkpoint)")
            return True
        return False

    res = train(cfg, tcfg, fail_injector=chaos)
    print(f"arch={args.arch} (reduced) steps={res.final_step} "
          f"restarts={res.restarts}")
    print(f"loss: first={res.losses[0]:.3f} last={res.losses[-1]:.3f}")
    assert res.losses[-1] < res.losses[0], "loss should decrease"
    print("ok: trained through a failure with checkpoint/restore")


if __name__ == "__main__":
    main()
