"""Detection inference + evaluation tour (reference:
models/maskrcnn/MaskRCNN.scala inference zoo entry +
optim/ValidationMethod.scala:230-756 MeanAveragePrecision family):
run the MaskRCNN-style inference model on a synthetic image, then score
detections with VOC and COCO-style mAP.

    JAX_PLATFORMS=cpu python examples/detection_eval.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from bigdl_tpu.models import maskrcnn                         # noqa: E402
from bigdl_tpu.optim.detection_metrics import (               # noqa: E402
    MeanAveragePrecision)


def run_maskrcnn():
    model = maskrcnn.build(num_classes=3, backbone_channels=(8, 16, 24, 32),
                           fpn_channels=16, pre_nms_topk=64,
                           post_nms_topk=16, max_detections=8)
    params, state = model.init(jax.random.PRNGKey(0))
    img = jnp.asarray(np.random.RandomState(0).rand(1, 64, 64, 3),
                      jnp.float32)
    out, _ = model.apply(params, state, img)
    n = int(out["valid"].sum())
    print(f"[maskrcnn] {n} detections, boxes {out['boxes'].shape}, "
          f"masks {out['masks'].shape} (static shapes, jit-able)")


def score_detector():
    """mAP on a hand-checkable fixture: 2 images, 2 classes."""
    # image 0: one gt of class 0 — detector finds it (IoU 1.0) plus a
    # confident false positive of class 1
    # image 1: one gt of each class — detector finds class 1 only
    outputs = [
        (np.array([[10, 10, 50, 50], [0, 0, 20, 20]], np.float32),
         np.array([0.9, 0.8], np.float32),
         np.array([0, 1], np.int32)),
        (np.array([[30, 30, 60, 60]], np.float32),
         np.array([0.7], np.float32),
         np.array([1], np.int32)),
    ]
    targets = [
        (np.array([[10, 10, 50, 50]], np.float32),
         np.array([0], np.int32)),
        (np.array([[30, 30, 60, 60], [5, 5, 25, 25]], np.float32),
         np.array([1, 0], np.int32)),
    ]
    voc = MeanAveragePrecision(num_classes=2, iou=0.5)
    res = voc.batch(outputs, targets)
    print(f"[voc  ] mAP@0.5 = {res.result:.4f}  "
          f"per-class = {voc.per_class()}")
    # class 0: 1 of 2 gts found at full IoU -> AP 0.5; class 1: found its
    # only gt but the image-0 FP ranks above it -> AP 0.5
    assert abs(res.result - 0.5) < 1e-6
    coco = MeanAveragePrecision(num_classes=2, coco=True)
    print(f"[coco ] mAP@[.5:.95] = "
          f"{coco.batch(outputs, targets).result:.4f}")


def train_from_shards():
    """Detection training over the v2 sharded record path (reference:
    COCOSeqFileGenerator.scala seq-files feeding distributed detection
    training): synthetic detection shards → ShardedDetectionDataset with
    padded fixed-shape GT batches → RPN head trained with
    assign_anchor_targets/rpn_loss inside one jitted step."""
    import tempfile

    from bigdl_tpu.dataset.sharded import (
        ShardedDetectionDataset, generate_synthetic_detection)
    from bigdl_tpu.nn import SpatialConvolution
    from bigdl_tpu.nn.detection import Anchor, rpn_loss

    tmp = tempfile.mkdtemp()
    generate_synthetic_detection(tmp, n=64, num_shards=4, height=48,
                                 width=48, classes=2, seed=0)
    ds = ShardedDetectionDataset(tmp, batch_size=8, max_objects=8,
                                 shuffle=True, seed=1,
                                 transform=lambda im, t:
                                 (im.astype(np.float32) / 255.0, t))

    stride = 8
    anchor = Anchor(ratios=(0.5, 1.0, 2.0), scales=(2.0, 4.0))
    na = anchor.num
    # tiny two-stage backbone to the stride-8 map + RPN heads
    bb1 = SpatialConvolution(3, 16, 5, 5, 4, 4, 2, 2)
    bb2 = SpatialConvolution(16, 32, 3, 3, 2, 2, 1, 1)
    head_cls = SpatialConvolution(32, na, 1, 1)
    head_box = SpatialConvolution(32, na * 4, 1, 1)
    rng = jax.random.PRNGKey(0)
    params = {}
    for name, mod in (("bb1", bb1), ("bb2", bb2), ("cls", head_cls),
                      ("box", head_box)):
        rng, sub = jax.random.split(rng)
        params[name], _ = mod.init(sub)
    anchors = anchor.generate(6, 6, stride)              # 48/8 = 6

    @jax.jit
    def step(params, x, boxes, valid):
        def loss_fn(p):
            f = jax.nn.relu(bb1.forward(p["bb1"], x))
            f = jax.nn.relu(bb2.forward(p["bb2"], f))
            logits = head_cls.forward(p["cls"], f).reshape(x.shape[0], -1)
            deltas = head_box.forward(p["box"], f).reshape(
                x.shape[0], -1, 4)
            loss, (cl, bl) = rpn_loss(logits, deltas, anchors, boxes,
                                      valid, pos_iou=0.5, neg_iou=0.2)
            return loss, (cl, bl)
        (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params = jax.tree.map(lambda p, gg: p - 0.05 * gg, params, g)
        return params, loss, aux

    first = last = None
    for epoch in range(18):
        for x, t in ds:
            params, loss, (cl, bl) = step(
                params, jnp.asarray(x),
                jnp.asarray(t["boxes"]), jnp.asarray(t["valid"]))
            if first is None:
                first = float(loss)
            last = float(loss)
    print(f"[shards] RPN trained from v2 record shards: "
          f"loss {first:.3f} -> {last:.3f}")
    assert last < 0.5 * first, (first, last)


def finetune_and_map():
    """End-to-end MaskRCNN: fine-tune every head on COCO-format synthetic
    shards, then report box + mask mAP on held-out images (reference:
    models/maskrcnn/MaskRCNN.scala + ValidationMethod's MAP family)."""
    import tempfile

    from bigdl_tpu.dataset.sharded import (
        ShardedDetectionDataset, generate_synthetic_detection)

    tmp = tempfile.mkdtemp()
    generate_synthetic_detection(tmp, n=48, num_shards=2, height=64,
                                 width=64, classes=2, max_objects=3,
                                 seed=0)
    ds = ShardedDetectionDataset(
        tmp, batch_size=4, max_objects=4, shuffle=True, seed=1,
        with_masks=True,
        transform=lambda im, t: (im.astype(np.float32) / 255.0, t))
    model = maskrcnn.build(
        num_classes=2, backbone_channels=(16, 32, 48, 64),
        fpn_channels=32, pre_nms_topk=128, post_nms_topk=32,
        max_detections=8, mask_resolution=7, score_thresh=0.5,
        anchor_scales=(2.0, 4.0))
    params, state, (first, last) = maskrcnn.finetune(
        model, ds, epochs=20, lr=2e-3)
    print(f"[finetune] maskrcnn loss {first:.3f} -> {last:.3f}")

    generate_synthetic_detection(tmp + "_eval", n=12, num_shards=1,
                                 height=64, width=64, classes=2,
                                 max_objects=3, seed=9)
    eds = ShardedDetectionDataset(
        tmp + "_eval", batch_size=1, max_objects=4, with_masks=True,
        transform=lambda im, t: (im.astype(np.float32) / 255.0, t))
    images, targets = [], []
    for x, t in eds:
        gtv = t["valid"][0].astype(bool)
        images.append(x[0])
        targets.append((t["boxes"][0][gtv], t["classes"][0][gtv],
                        t["masks"][0][gtv]))
    box_map, mask_map = maskrcnn.evaluate_map(
        model, params, state, images, targets, (64, 64), num_classes=2)
    print(f"[finetune] box mAP@0.5 = {box_map:.3f}, "
          f"mask mAP@0.5 = {mask_map:.3f}")
    assert last < 0.3 * first, (first, last)


def main():
    run_maskrcnn()
    score_detector()
    train_from_shards()
    finetune_and_map()
    print("detection tour complete (COCO json + RLE utilities: "
          "bigdl_tpu/dataset/segmentation.py)")


if __name__ == "__main__":
    main()
