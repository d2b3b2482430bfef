"""Train + commit the repo's vision backbone: ShapesResNet20 (VERDICT r4 #6).

Reference capability: a populated pretrained-model repository
(``downloader/ModelDownloader.scala:26-112``, ``DefaultModelRepo:112``).
Zero egress means no CIFAR/ImageNet; the committed backbone is a CIFAR-scale
ResNet-20 GENUINELY TRAINED in-tree on the procedural shapes corpus
(``mmlspark_tpu/dl/procedural_shapes.py`` — openly synthetic), then
transfer-evaled on REAL data: frozen-feature logistic probe on the UCI
digits scans vs the same probe on raw pixels.  Both numbers land in
eval.json and the committed example asserts the lift.

    nohup python tools/train_backbone.py > bench_attempts/backbone.log 2>&1 &

Flags: --epochs N --width W --n-train N --cpu (force CPU platform).
One process, no children: on the chip it is the process that holds it.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
REPO_DIR = os.path.join(ROOT, "artifacts", "model_repo")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=16)
    ap.add_argument("--width", type=int, default=32)
    ap.add_argument("--n-train", type=int, default=50_000)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from mmlspark_tpu.utils.device import enable_compilation_cache
    enable_compilation_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    print(f"devices: {jax.devices()}", flush=True)

    from mmlspark_tpu.dl.procedural_shapes import make_shapes, digits_as_images
    from mmlspark_tpu.models.resnet import cifar_resnet20

    t0 = time.perf_counter()
    Xtr, ytr = make_shapes(args.n_train, seed=0)
    Xte, yte = make_shapes(8_000, seed=1)
    print(f"data: {Xtr.shape} in {time.perf_counter() - t0:.0f}s", flush=True)

    dtype = jnp.float32 if args.cpu else jnp.bfloat16
    model = cifar_resnet20(num_classes=10, width=args.width, dtype=dtype)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)),
                          train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    steps_per_epoch = args.n_train // args.batch
    total_steps = steps_per_epoch * args.epochs
    sched = optax.cosine_decay_schedule(0.1, total_steps)
    tx = optax.chain(optax.add_decayed_weights(1e-4),
                     optax.sgd(sched, momentum=0.9, nesterov=True))
    opt = tx.init(params)

    @jax.jit
    def train_step(params, batch_stats, opt, xb, yb):
        def loss_fn(p):
            logits, mut = model.apply({"params": p, "batch_stats": batch_stats},
                                      xb, train=True, mutable=["batch_stats"])
            l = optax.softmax_cross_entropy_with_integer_labels(
                logits, yb).mean()
            return l, (mut["batch_stats"], logits)
        (l, (bs, logits)), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        up, opt = tx.update(g, opt, params)
        params = optax.apply_updates(params, up)
        acc = (logits.argmax(-1) == yb).mean()
        return params, bs, opt, l, acc

    @jax.jit
    def eval_logits(params, batch_stats, xb):
        return model.apply({"params": params, "batch_stats": batch_stats}, xb)

    @jax.jit
    def eval_features(params, batch_stats, xb):
        return model.apply({"params": params, "batch_stats": batch_stats}, xb,
                           features=True)

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for ep in range(args.epochs):
        order = rng.permutation(args.n_train)[:steps_per_epoch * args.batch]
        ep_l = ep_a = 0.0
        for i in range(steps_per_epoch):
            sl = order[i * args.batch:(i + 1) * args.batch]
            params, batch_stats, opt, l, a = train_step(
                params, batch_stats, opt, jnp.asarray(Xtr[sl]),
                jnp.asarray(ytr[sl]))
            ep_l += float(l); ep_a += float(a)
        print(f"epoch {ep + 1}/{args.epochs} loss {ep_l / steps_per_epoch:.4f} "
              f"acc {ep_a / steps_per_epoch:.4f} "
              f"({time.perf_counter() - t0:.0f}s)", flush=True)

    def batched(fn, X, bs=1000):
        return np.concatenate([np.asarray(fn(params, batch_stats,
                                             jnp.asarray(X[a:a + bs])))
                               for a in range(0, len(X), bs)])

    te_acc = float((batched(eval_logits, Xte).argmax(-1) == yte).mean())
    print(f"shapes held-out acc {te_acc:.4f}", flush=True)

    # ---- transfer eval on REAL digits (position/scale-jittered protocol,
    # see digits_as_images): frozen features vs raw pixels
    from sklearn.linear_model import LogisticRegression
    Xd, yd = digits_as_images(jitter=True)
    cut = int(len(yd) * 0.7)
    rngd = np.random.default_rng(7)
    order = rngd.permutation(len(yd))
    tr, te = order[:cut], order[cut:]
    feats = batched(eval_features, Xd)
    probe = LogisticRegression(max_iter=2000).fit(feats[tr], yd[tr])
    transfer_acc = float(probe.score(feats[te], yd[te]))
    raw = Xd.reshape(len(Xd), -1)
    probe_raw = LogisticRegression(max_iter=2000).fit(raw[tr], yd[tr])
    raw_acc = float(probe_raw.score(raw[te], yd[te]))
    print(f"digits transfer: frozen-features {transfer_acc:.4f} "
          f"vs raw pixels {raw_acc:.4f}", flush=True)

    # ---- persist via the repo machinery (f32 weights; featurizer-ready)
    from mmlspark_tpu.dl.jax_model import FlaxModelPayload
    from mmlspark_tpu.dl.model_downloader import ModelRepo, ModelSchema
    model_f32 = cifar_resnet20(num_classes=10, width=args.width)
    var_f32 = {"params": jax.tree.map(lambda a: np.asarray(a, np.float32), params),
               "batch_stats": jax.tree.map(lambda a: np.asarray(a, np.float32),
                                           batch_stats)}
    payload = FlaxModelPayload(module=model_f32, variables=var_f32)
    repo = ModelRepo(REPO_DIR)
    schema = ModelSchema(name="ShapesResNet20",
                         dataset=f"procedural-shapes-{args.n_train}",
                         model_type="classification", input_shape=[32, 32, 3],
                         num_outputs=10)
    path = repo.save_model(schema, payload)
    with open(os.path.join(path, "eval.json"), "w") as f:
        json.dump({"train_corpus": f"procedural shapes {args.n_train} "
                                   "(synthetic, dl/procedural_shapes.py, seed 0)",
                   "epochs": args.epochs, "width": args.width,
                   "shapes_holdout_acc": round(te_acc, 4),
                   "transfer_protocol": "UCI digits placed at random "
                       "position/scale on a 32x32 canvas (seed 11); "
                       "logistic probe on frozen pooled features vs on "
                       "raw pixels, same split",
                   "digits_transfer_frozen_features_acc": round(transfer_acc, 4),
                   "digits_raw_pixel_probe_acc": round(raw_acc, 4),
                   "train_seconds": round(time.perf_counter() - t0, 1),
                   "platform": str(jax.devices()[0].platform)}, f, indent=1)
    print(f"saved {path}", flush=True)
    print("BACKBONE_DONE", flush=True)


if __name__ == "__main__":
    main()
