"""Full-fidelity MaskRCNN (VERDICT r3 next #5): ResNet-50-FPN backbone
option, end-to-end head training on COCO-format fixtures, and box+mask
mAP above a fixed floor with ground truth loaded through the COCO
instances JSON path (reference: models/maskrcnn/MaskRCNN.scala,
optim/ValidationMethod.scala:230-756)."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.dataset.segmentation import COCODataset, rle_encode
from bigdl_tpu.dataset.sharded import (ShardedDetectionDataset,
                                       generate_synthetic_detection)
from bigdl_tpu.models import maskrcnn, resnet


def test_resnet50_fpn_backbone_builds_and_runs():
    """The zoo ResNet-50 trunk (23.5M params, C2..C5 at strides 4-32)
    swaps in for the stand-in backbone."""
    t = resnet.trunk(50)
    assert t.channels == [256, 512, 1024, 2048]
    # the trunk alone in the abstract (its count and its four shapes are
    # all that is asserted of it); it runs for real inside the model below
    x = jnp.zeros((1, 64, 64, 3))

    def trunk(key, x):
        p, s = t.init(key)
        return p, t.apply(p, s, x)[0]
    p, outs = jax.eval_shape(trunk, jax.random.PRNGKey(0), x)
    from bigdl_tpu.core.module import count_params
    n = count_params(p)
    assert 23_000_000 < n < 24_000_000, n   # ResNet-50 minus the fc head
    assert [o.shape for o in outs] == [
        (1, 16, 16, 256), (1, 8, 8, 512), (1, 4, 4, 1024), (1, 2, 2, 2048)]

    m = maskrcnn.build(num_classes=3, backbone="resnet50",
                       pre_nms_topk=32, post_nms_topk=8, max_detections=4)
    mp, ms = m.init(jax.random.PRNGKey(1))
    # (jitted: one program, where the eager forward compiles each of the
    # backbone's 53 convolutions on its own)
    out, _ = jax.jit(m.apply)(mp, ms, x)
    assert out["boxes"].shape == (4, 4)
    assert out["masks"].shape == (4, 28, 28)


def _coco_json_from_eval(tmp_path, eds):
    """Write the held-out set as a COCO instances JSON (bbox xywh +
    uncompressed RLE segmentation) and return (images, coco_targets)
    loaded back through COCODataset — the fixture-format round trip."""
    images, raw = [], []
    doc = {"images": [], "annotations": [],
           "categories": [{"id": 7, "name": "a"}, {"id": 9, "name": "b"}]}
    cat_ids = [7, 9]
    aid = 1
    for i, (x, t) in enumerate(eds):
        images.append(x[0])
        gtv = t["valid"][0].astype(bool)
        doc["images"].append({"id": i, "file_name": f"{i}.png",
                              "height": 64, "width": 64})
        for b, c, m in zip(t["boxes"][0][gtv], t["classes"][0][gtv],
                           t["masks"][0][gtv]):
            x0, y0, x1, y1 = [float(v) for v in b]
            doc["annotations"].append({
                "id": aid, "image_id": i,
                "bbox": [x0, y0, x1 - x0, y1 - y0],
                "category_id": cat_ids[int(c)],
                "iscrowd": 0, "area": float((x1 - x0) * (y1 - y0)),
                "segmentation": {"counts": rle_encode(np.asarray(m, bool)),
                                 "size": [64, 64]}})
            aid += 1
        raw.append(t)
    path = str(tmp_path / "instances.json")
    with open(path, "w") as fh:
        json.dump(doc, fh)

    coco = COCODataset(path)
    targets = []
    for img in coco:
        boxes = np.asarray([a.xyxy for a in img.annotations], np.float32)
        labels = np.asarray([a.category for a in img.annotations],
                            np.int32)
        masks = np.stack([a.mask(64, 64) for a in img.annotations])
        targets.append((boxes, labels, masks))
    return images, targets


@pytest.mark.slow        # trains 24 epochs to a box and mask mAP floor (78 s)
def test_maskrcnn_trains_to_map_floor(tmp_path):
    """Train all heads end to end on synthetic COCO-format shards, then
    assert box AND mask mAP@0.5 above a fixed floor on held-out images
    whose ground truth round-trips through a COCO instances JSON."""
    train_dir = str(tmp_path / "train")
    generate_synthetic_detection(train_dir, n=48, num_shards=2, height=64,
                                 width=64, classes=2, max_objects=3,
                                 seed=0)
    ds = ShardedDetectionDataset(
        train_dir, batch_size=4, max_objects=4, shuffle=True, seed=1,
        with_masks=True,
        transform=lambda im, t: (im.astype(np.float32) / 255.0, t))
    model = maskrcnn.build(
        num_classes=2, backbone_channels=(16, 32, 48, 64),
        fpn_channels=32, pre_nms_topk=128, post_nms_topk=32,
        max_detections=8, mask_resolution=7, score_thresh=0.5,
        anchor_scales=(2.0, 4.0))
    # 24 epochs clears both mAP floors at seed 3; 35 made this the top
    # tier-1 offender at 112 s on the 1-core image
    params, state, (first, last) = maskrcnn.finetune(
        model, ds, epochs=24, lr=2e-3, rng=jax.random.PRNGKey(3))
    assert last < 0.2 * first, (first, last)

    eval_dir = str(tmp_path / "eval")
    generate_synthetic_detection(eval_dir, n=16, num_shards=1, height=64,
                                 width=64, classes=2, max_objects=3,
                                 seed=9)
    eds = ShardedDetectionDataset(
        eval_dir, batch_size=1, max_objects=4, with_masks=True,
        transform=lambda im, t: (im.astype(np.float32) / 255.0, t))
    images, targets = _coco_json_from_eval(tmp_path, eds)
    box_map, mask_map = maskrcnn.evaluate_map(
        model, params, state, images, targets, (64, 64), num_classes=2)
    assert box_map > 0.4, box_map
    assert mask_map > 0.4, mask_map
