"""Shared driver for the accelerator example variants (BSC/FP16/MPQ/HFA),
mirroring the shared structure of the reference's cnn_*.py family."""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))



def run(extra_args=(), config_fn=lambda a: {}, sync_default="fsa"):
    parser = argparse.ArgumentParser()
    parser.add_argument("-lr", "--learning-rate", type=float, default=0.01)
    parser.add_argument("-bs", "--batch-size", type=int, default=32)
    parser.add_argument("-ep", "--epoch", type=int, default=5)
    parser.add_argument("-sc", "--split-by-class", action="store_true")
    parser.add_argument("-c", "--cpu", action="store_true")
    parser.add_argument("-d", "--dataset", default="mnist",
                        choices=["mnist", "fashion-mnist", "cifar10", "synthetic"])
    parser.add_argument("--model", default="cnn")
    parser.add_argument("--augment", action="store_true",
                        help="random-crop + flip augmentation "
                             "(the CIFAR training recipe)")
    for flags_short, flags_long, typ, default in extra_args:
        parser.add_argument(flags_short, flags_long, type=typ, default=default)
    args = parser.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    # persistent compile cache: repeat demo runs start warm (same
    # directory as the tests, whatever the launch cwd)
    from geomx_tpu.utils import enable_compile_cache
    enable_compile_cache()

    from geomx_tpu import GeoConfig, HiPSTopology
    from geomx_tpu.data import load_dataset
    from geomx_tpu.models import get_model
    from geomx_tpu.optim import get_optimizer
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.train import Trainer

    overrides = dict(config_fn(args))
    overrides.setdefault("sync_mode", sync_default)
    cfg = GeoConfig.from_env(**overrides)
    topo = HiPSTopology(cfg.num_parties, cfg.workers_per_party)
    data = load_dataset(args.dataset, root=cfg.data_dir)
    if data["synthetic"] and args.dataset != "synthetic":
        print(f"# no local {args.dataset} data under {cfg.data_dir}: "
              'training on the synthetic substitute ("synthetic": true)')

    trainer = Trainer(get_model(args.model), topo,
                      get_optimizer("adam", learning_rate=args.learning_rate),
                      sync=get_sync_algorithm(cfg), config=cfg)
    state = trainer.init_state(jax.random.PRNGKey(0), data["train_x"][:2])
    loader = trainer.make_loader(data["train_x"], data["train_y"],
                                 args.batch_size,
                                 split_by_class=args.split_by_class,
                                 augment=args.augment)

    print(f"Start training on {topo.total_workers} workers "
          f"({topo.num_parties} parties x {topo.workers_per_party}), "
          f"sync={cfg.sync_mode}, compression={cfg.compression}, "
          f"dgt={cfg.enable_dgt}.")
    begin, it = time.time(), 0
    eval_every = getattr(args, "eval_every", 1)
    for epoch in range(args.epoch):
        for xb, yb in loader.epoch(epoch):
            state, metrics = trainer.train_step(state, xb, yb)
            metrics = jax.device_get(metrics)
            it += 1
            if it % eval_every == 0:
                acc = trainer.evaluate(state, data["test_x"], data["test_y"])
                print("[Time %.3f][Epoch %d][Iteration %d] Test Acc %.4f"
                      % (time.time() - begin, epoch, it, acc))
    return state, trainer
