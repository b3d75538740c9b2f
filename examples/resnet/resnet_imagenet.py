"""ResNet-50 / ImageNet-shape training from TFRecord image shards.

The BASELINE north-star workload (BASELINE.json: RDD/record-fed ResNet-50)
as a runnable example: file-sharded ImageNet-layout TFRecords ("image/
encoded" JPEG + "image/class/label") -> parallel decode + Inception-crop
augment -> shuffle -> batch -> device prefetch -> jitted donated train
step.  Maps the reference's resnet example, whose input path is the
upstream tf/models ImageNet pipeline (reference:
examples/resnet/README.md:3, resnet_cifar_dist.py:1-285 for the
conversion shape).

TPU-first: uint8 pixels cross host->HBM (4x less transfer than f32);
normalization fuses into the first conv inside the step
(image.normalize_batch).  Default model is the normalizer-free ResNet-50
(--norm none), the variant with the least HBM traffic (speed against
GroupNorm on this chip: not measured).

Standalone:
    python examples/resnet/resnet_imagenet.py --synth --steps 20
Cluster (each worker reads its shard slice):
    python examples/resnet/resnet_imagenet.py --data_dir /path/shards \
        --cluster_size 2
"""
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..")))

import argparse


def build_argparser():
    p = argparse.ArgumentParser()
    p.add_argument("--data_dir", default=None,
                   help="dir of TFRecord shards (train-*); --synth to "
                        "generate a small synthetic set")
    p.add_argument("--synth", action="store_true",
                   help="write synthetic JPEG shards into --data_dir "
                        "(or a temp dir) first")
    p.add_argument("--synth_examples", type=int, default=512)
    p.add_argument("--num_classes", type=int, default=1000)
    p.add_argument("--image_size", type=int, default=224)
    p.add_argument("--steps", type=int, default=50,
                   help="step cap; 0 = train --epochs full passes instead")
    p.add_argument("--epochs", type=int, default=1,
                   help="passes over the shards (only when --steps 0)")
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--norm", default="none",
                   choices=["none", "group", "batch"])
    p.add_argument("--reader_threads", type=int, default=4)
    p.add_argument("--shuffle_buffer", type=int, default=2048)
    p.add_argument("--indexed", action="store_true",
                   help="random-access shards via sidecar indexes: exact "
                        "global shuffle + balanced record-granular "
                        "sharding (data.Dataset.from_indexed_tfrecords)")
    p.add_argument("--learning_rate", type=float, default=0.1)
    p.add_argument("--model_dir", default=None)
    p.add_argument("--platform", choices=["cpu", "tpu"], default="cpu")
    p.add_argument("--cluster_size", type=int, default=1)
    return p


def write_synth_shards(out_dir, n, num_classes, size=64, num_shards=4,
                       prefix="train", seed=0):
    """Class-template JPEGs (learnable, like the cifar example's synthetic
    set) in the ImageNet shard layout."""
    import numpy as np

    from tensorflowonspark_tpu import image

    rng = np.random.RandomState(seed)
    tmpl_rng = np.random.RandomState(0)   # templates shared across splits
    templates = tmpl_rng.randint(0, 255,
                                 (min(num_classes, 16), size, size, 3))

    def records():
        for i in range(n):
            label = i % len(templates)
            img = np.clip(0.7 * templates[label]
                          + 0.3 * rng.randint(0, 255, (size, size, 3)),
                          0, 255).astype(np.uint8)
            yield img, label
    return image.write_image_shards(records(), out_dir,
                                    num_shards=num_shards, prefix=prefix)


def main_fun(args, ctx):
    """The training program (argv-style args, framework ctx)."""
    if isinstance(args, list):
        args = build_argparser().parse_args(args)
    from tensorflowonspark_tpu import util as fw_util

    if getattr(args, "platform", "cpu") == "cpu":
        fw_util.pin_platform("cpu")
    import glob

    import jax
    if ctx is not None:
        ctx.init_distributed()
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu import image
    from tensorflowonspark_tpu.data import Dataset
    from tensorflowonspark_tpu.models.resnet import ResNet50
    from tensorflowonspark_tpu.optim import make_optimizer
    from tensorflowonspark_tpu.parallel import train as train_mod
    from tensorflowonspark_tpu.utils import checkpoint as ckpt_mod

    num_workers = ctx.num_processes if ctx is not None else 1
    worker = ctx.process_id if ctx is not None else 0

    paths = sorted(glob.glob(os.path.join(args.data_dir, "train-*")))
    assert paths, f"no train-* shards under {args.data_dir}"

    # each worker reads its slice of the shard list (file-level sharding,
    # like the reference's per-executor RDD partitions)
    if args.steps > 0 and args.epochs != 1:
        print(f"[worker {worker}] note: --steps {args.steps} bounds "
              "training; --epochs only applies with --steps 0", flush=True)
    model = ResNet50(num_classes=args.num_classes, norm=args.norm)
    rng = jax.random.key(worker)
    init_img = jnp.zeros((1, args.image_size, args.image_size, 3),
                         jnp.uint8)
    params = model.init(rng, image.normalize_batch(init_img))["params"]

    def loss_fn(p, batch, _rng):
        imgs_u8, labels = batch
        x = image.normalize_batch(imgs_u8)        # fuses into conv_init
        logits = model.apply({"params": p}, x)
        onehot = jax.nn.one_hot(labels, args.num_classes, dtype=jnp.float32)
        return -jnp.mean(jnp.sum(
            jax.nn.log_softmax(logits.astype(jnp.float32)) * onehot,
            axis=-1))

    opt, _ = make_optimizer("sgd", learning_rate=args.learning_rate,
                            momentum=0.9)
    state = train_mod.create_train_state(params, opt)
    step = train_mod.make_train_step(loss_fn, opt, donate=True)

    # full-state resume (params + optimizer moments + step); the input
    # pipeline then skips the records already consumed — mid-epoch resume
    # the reference's epoch-boundary TF callbacks could not do
    resume_step = 0
    if args.model_dir:
        restored, found = ckpt_mod.restore_checkpoint(args.model_dir, state)
        if restored is not None:
            state, resume_step = restored, int(found or 0)
            print(f"[worker {worker}] resumed at step {resume_step}",
                  flush=True)

    tf_fn = image.train_transform(args.image_size, seed=1234 + worker)
    if args.indexed:
        # indexed root: sidecar indexes give an EXACT per-epoch global
        # shuffle and balanced record-granular shards (no interleave or
        # reservoir needed) — blocks of 16 compressed examples per ranged
        # read keep the IO mostly sequential
        ds = (Dataset.from_indexed_tfrecords(paths, global_shuffle=True,
                                             seed=1234, shuffle_block=16)
              .shard(num_workers, worker)
              .repeat(None if args.steps > 0 else args.epochs))
    else:
        ds = (Dataset.from_tfrecords(paths)
              # interleave BEFORE shard so BOTH shard paths see mixed
              # files: file-granular sharding copies the interleave spec
              # (each worker round-robins its own files), and
              # record-granular sharding (more workers than files)
              # strides the already-interleaved stream — either way the
              # reservoir shuffle mixes across the whole slice instead of
              # a buffer-sized window of one file
              .interleave(cycle_length=4)
              .shard(num_workers, worker)
              # shuffle compressed examples (KBs each), then decode in
              # threads
              .shuffle(args.shuffle_buffer, seed=worker)
              .repeat(None if args.steps > 0 else args.epochs))
    if resume_step:
        # deterministic pipeline: skip the records consumed so far —
        # BEFORE the decode map, so skipping discards KB-scale compressed
        # examples instead of JPEG-decoding millions just to drop them
        ds = ds.skip(resume_step * args.batch_size)
    ds = (ds.map(tf_fn, num_parallel=args.reader_threads)
            .batch(args.batch_size))

    # preemption safety: SIGTERM (TPU preemption / executor decommission)
    # commits a final checkpoint before the process dies
    holder = {"state": state}
    handler = None
    if args.model_dir and (ctx is None or ctx.is_chief):
        handler = ckpt_mod.install_preemption_handler(
            lambda: ckpt_mod.save_checkpoint(
                args.model_dir, holder["state"],
                int(np.asarray(holder["state"].step))))

    import contextlib
    guard = (handler.guard if handler is not None
             else contextlib.nullcontext)

    losses = []
    metrics = None
    already_done = args.steps > 0 and resume_step >= args.steps
    if not already_done:
        for i, batch in enumerate(ds.prefetch_to_device()):
            if args.steps > 0 and resume_step + i >= args.steps:
                break
            # guard: the donated input state is deleted at dispatch, so a
            # SIGTERM inside the step would catch holder["state"] mid-
            # donation — block it until the fresh state is published
            with guard():
                state, metrics = step(state, batch, rng)
                holder["state"] = state
            if i % 10 == 0:
                losses.append(float(np.asarray(metrics["loss"])))
                print(f"[worker {worker}] step {resume_step + i} "
                      f"loss={losses[-1]:.4f}", flush=True)
        if metrics is None and resume_step == 0:
            raise RuntimeError(
                f"worker {worker}: shard slice produced no full batches "
                f"(batch_size={args.batch_size}, {len(paths)} shards, "
                f"{num_workers} workers) — lower --batch_size or use fewer "
                "workers than shard files")
        if metrics is None:   # resumed past the remaining data: benign
            final = float("nan")
            print(f"[worker {worker}] resumed at step {resume_step}: no "
                  "batches left to train; continuing to eval/save",
                  flush=True)
        else:
            final = float(np.asarray(metrics["loss"]))
            print(f"[worker {worker}] done: first={losses[0]:.4f} "
                  f"final={final:.4f}", flush=True)
    else:
        final = float("nan")
        print(f"[worker {worker}] checkpoint already at step {resume_step} "
              f">= --steps {args.steps}; skipping training", flush=True)

    # validation pass (chief only): validation-* shards through the
    # deterministic center-crop transform, top-1 accuracy on device
    val_paths = sorted(glob.glob(os.path.join(args.data_dir,
                                              "validation-*")))
    if val_paths and (ctx is None or ctx.is_chief):
        eval_ds = (Dataset.from_tfrecords(val_paths)
                   .map(image.eval_transform(args.image_size),
                        num_parallel=args.reader_threads)
                   .batch(args.batch_size, drop_remainder=False,
                          pad_tail=False))

        @jax.jit
        def eval_step(p, imgs_u8, labels):
            logits = model.apply(
                {"params": p}, image.normalize_batch(imgs_u8))
            return jnp.sum(jnp.argmax(logits, -1) == labels)

        correct = total = 0
        for imgs_u8, labels in eval_ds:
            n = len(labels)
            if n < args.batch_size:
                # pad the ragged tail up to the ONE compiled shape; padded
                # labels are -1, which argmax never produces, so they
                # cannot count as correct
                reps = args.batch_size - n
                imgs_u8 = np.concatenate(
                    [imgs_u8, np.repeat(imgs_u8[-1:], reps, axis=0)])
                labels = np.concatenate(
                    [labels, np.full(reps, -1, labels.dtype)])
            correct += int(np.asarray(eval_step(
                state.params, jnp.asarray(imgs_u8), jnp.asarray(labels))))
            total += n
        if total:
            print(f"[worker {worker}] validation top-1 "
                  f"{correct / total:.4f} ({correct}/{total})", flush=True)

    if args.model_dir and (ctx is None or ctx.is_chief):
        ckpt_mod.save_checkpoint(args.model_dir, state, step=int(
            np.asarray(state.step)))
    if handler is not None:
        handler.uninstall()  # clean shutdown: a late SIGTERM must not re-save
    return final


def main(argv=None):
    args = build_argparser().parse_args(argv)
    if args.synth:
        import tempfile
        args.data_dir = args.data_dir or tempfile.mkdtemp(
            prefix="imagenet-synth-")
        # independent sentinels: a data_dir from an older run may hold
        # train shards but no validation shards
        if not os.path.exists(os.path.join(
                args.data_dir, "train-00000-of-00004")):
            write_synth_shards(args.data_dir, args.synth_examples,
                               args.num_classes)
        if not os.path.exists(os.path.join(
                args.data_dir, "validation-00000-of-00002")):
            write_synth_shards(args.data_dir,
                               max(args.synth_examples // 8, 16),
                               args.num_classes, num_shards=2,
                               prefix="validation", seed=1)
        print(f"synthetic shards in {args.data_dir}")
    if args.cluster_size > 1:
        from tensorflowonspark_tpu import backend, cluster
        c = cluster.run(backend.LocalBackend(args.cluster_size),
                        main_fun, tf_args=args,
                        input_mode=cluster.InputMode.NATIVE)
        c.shutdown()
    else:
        main_fun(args, None)


if __name__ == "__main__":
    main()
