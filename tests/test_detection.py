"""Detection ops, sparse layers, and dlframes tests (reference analogues:
nn/NmsSpec, AnchorSpec, RoiAlignSpec, SparseLinearSpec, DLEstimatorSpec)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.detection import (Anchor, DetectionOutputSSD, FPN, Nms,
                                    Pooler, PriorBox, RoiAlign, box_iou,
                                    decode_boxes, encode_boxes, nms,
                                    roi_align)
from bigdl_tpu.nn.sparse import (LookupTableSparse, SparseCOO,
                                 SparseJoinTable, SparseLinear)
from bigdl_tpu.dlframes import DLClassifier, DLEstimator


def test_box_iou_known():
    a = jnp.asarray([[0, 0, 10, 10]], jnp.float32)
    b = jnp.asarray([[0, 0, 10, 10], [5, 5, 15, 15], [20, 20, 30, 30]],
                    jnp.float32)
    iou = np.asarray(box_iou(a, b))[0]
    np.testing.assert_allclose(iou, [1.0, 25 / 175, 0.0], rtol=1e-5)


def test_nms_suppresses_overlaps():
    boxes = jnp.asarray([[0, 0, 10, 10], [1, 1, 11, 11], [20, 20, 30, 30]],
                        jnp.float32)
    scores = jnp.asarray([0.9, 0.8, 0.7])
    idx, valid = nms(boxes, scores, iou_threshold=0.5, max_output=3)
    kept = np.asarray(idx)[np.asarray(valid)]
    np.testing.assert_array_equal(kept, [0, 2])


def test_nms_jittable():
    boxes = jnp.asarray(np.random.RandomState(0).rand(50, 4) * 100,
                        jnp.float32)
    boxes = boxes.at[:, 2:].set(boxes[:, :2] + 10)
    scores = jnp.asarray(np.random.RandomState(1).rand(50), jnp.float32)
    idx, valid = jax.jit(lambda b, s: nms(b, s, 0.5, 10))(boxes, scores)
    assert idx.shape == (10,)
    assert bool(valid[0])


def test_box_encode_decode_roundtrip():
    r = np.random.RandomState(0)
    anchors = r.rand(20, 4).astype(np.float32) * 50
    anchors[:, 2:] = anchors[:, :2] + 10 + r.rand(20, 2) * 20
    gt = anchors + r.randn(20, 4).astype(np.float32)
    deltas = encode_boxes(jnp.asarray(anchors), jnp.asarray(gt))
    back = decode_boxes(jnp.asarray(anchors), deltas)
    np.testing.assert_allclose(np.asarray(back), gt, atol=1e-3)


def test_anchor_generation():
    a = Anchor(ratios=(0.5, 1.0, 2.0), scales=(8.0,))
    boxes = a.generate(4, 5, stride=16)
    assert boxes.shape == (4 * 5 * 3, 4)
    # centers at (stride/2 + i*stride)
    c = np.asarray(boxes[:3])
    np.testing.assert_allclose((c[:, 0] + c[:, 2]) / 2, 8.0, atol=1e-4)
    # ratio 1 anchor is square
    w = c[1, 2] - c[1, 0]
    h = c[1, 3] - c[1, 1]
    np.testing.assert_allclose(w, h, rtol=1e-5)


def test_priorbox_normalized():
    pb = PriorBox(min_sizes=(30,), max_sizes=(60,), aspect_ratios=(2.0,))
    boxes = pb.generate(2, 2, 300, 300)
    # per cell: min, sqrt(min*max), 2:1, 1:2 → 4 priors
    assert boxes.shape == (2 * 2 * 4, 4)
    assert float(boxes.min()) > -1.0 and float(boxes.max()) < 2.0


def test_roi_align_constant_region():
    feat = jnp.ones((1, 16, 16, 3)) * 5.0
    boxes = jnp.asarray([[2.0, 2.0, 10.0, 10.0]])
    # (jitted, as the other calls below: one program, where the eager
    # call compiles each op of the sampling grid on its own)
    out = jax.jit(lambda f, b: roi_align(f, b, jnp.asarray([0]), (4, 4)))(
        feat, boxes)
    assert out.shape == (1, 4, 4, 3)
    np.testing.assert_allclose(np.asarray(out), 5.0, rtol=1e-5)


def test_roi_align_gradient_flows():
    feat = jnp.asarray(np.random.RandomState(0).rand(1, 8, 8, 2),
                       jnp.float32)
    boxes = jnp.asarray([[1.0, 1.0, 6.0, 6.0]])

    def f(feat):
        return roi_align(feat, boxes, jnp.asarray([0]), (2, 2)).sum()

    g = jax.jit(jax.grad(f))(feat)
    assert float(jnp.abs(g).sum()) > 0


def test_fpn_shapes():
    fpn = FPN([8, 16], out_channels=4)
    params, state = fpn.init(jax.random.PRNGKey(0))
    c3 = jnp.zeros((1, 8, 8, 8))
    c4 = jnp.zeros((1, 4, 4, 16))
    outs, _ = jax.jit(fpn.apply)(params, state, (c3, c4))
    assert outs[0].shape == (1, 8, 8, 4)
    assert outs[1].shape == (1, 4, 4, 4)


def test_detection_output_ssd():
    priors = jnp.asarray([[10, 10, 20, 20], [50, 50, 60, 60]], jnp.float32)
    loc = jnp.zeros((2, 4))
    conf = jnp.asarray([[0.1, 0.9], [0.8, 0.2]])
    head = DetectionOutputSSD(n_classes=2, top_k=2, background_id=0)
    boxes, scores, valid = jax.jit(head.forward)({}, priors, loc, conf)
    assert boxes.shape == (2, 2, 4)
    assert not bool(valid[0].any())          # background zeroed
    assert bool(valid[1, 0])
    np.testing.assert_allclose(float(scores[1, 0]), 0.9, rtol=1e-5)


def test_sparse_linear_matches_dense():
    r = np.random.RandomState(0)
    dense = r.rand(4, 32).astype(np.float32)
    dense[dense < 0.8] = 0.0
    sp = SparseCOO.from_dense(dense, nnz_per_row=10)
    np.testing.assert_allclose(np.asarray(sp.to_dense()), dense, rtol=1e-6)
    layer = SparseLinear(32, 8)
    params, state = layer.init(jax.random.PRNGKey(0))
    out = layer.forward(params, sp)
    ref = jnp.asarray(dense) @ params["weight"] + params["bias"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4)


def test_lookup_table_sparse_combiners():
    ids = np.asarray([[0, 1, -1], [2, -1, -1]])
    vals = np.asarray([[1.0, 1.0, 0.0], [2.0, 0.0, 0.0]])
    sp = SparseCOO(ids, vals, n_cols=4)
    for comb in ("sum", "mean", "sqrtn"):
        layer = LookupTableSparse(4, 6, combiner=comb)
        params, _ = layer.init(jax.random.PRNGKey(0))
        out = layer.forward(params, sp)
        assert out.shape == (2, 6)
    mean_l = LookupTableSparse(4, 6, combiner="mean")
    params, _ = mean_l.init(jax.random.PRNGKey(0))
    out = np.asarray(mean_l.forward(params, sp))
    w = np.asarray(params["weight"])
    np.testing.assert_allclose(out[0], (w[0] + w[1]) / 2, rtol=1e-5)


def test_sparse_join_table():
    a = SparseCOO(np.asarray([[0, -1]]), np.asarray([[1.0, 0.0]]), 3)
    b = SparseCOO(np.asarray([[1, 2]]), np.asarray([[2.0, 3.0]]), 4)
    j = SparseJoinTable().forward({}, a, b)
    assert j.n_cols == 7
    dense = np.asarray(j.to_dense())
    np.testing.assert_allclose(dense, [[1, 0, 0, 0, 2, 3, 0]])


def test_dl_classifier_fit_transform():
    r = np.random.RandomState(0)
    x = r.randn(128, 4).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32)
    df = {"features": x, "label": y}
    est = DLClassifier(
        nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2),
                      nn.LogSoftMax()),
        nn.ClassNLLCriterion(), feature_size=(4,), max_epoch=30,
        learning_rate=0.1, batch_size=32)
    model = est.fit(df)
    out = model.transform(df)
    assert out["prediction"].shape == (128,)
    acc = (out["prediction"] == y).mean()
    assert acc > 0.9, acc
    assert "features" in out    # passthrough columns kept


def test_pooler_level_assignment():
    """Canonical-size boxes go to the second-coarsest level (FPN eq. 1)."""
    pooler = Pooler((2, 2), scales=(0.25, 0.125, 0.0625, 0.03125),
                    canonical_size=224.0)
    feats = [jnp.zeros((1, s, s, 2)) for s in (64, 32, 16, 8)]
    # put a recognizable constant on each level
    feats = [f + i for i, f in enumerate(feats)]
    boxes = jnp.asarray([
        [0, 0, 224, 224],      # canonical -> level index 2
        [0, 0, 56, 56],        # 1/4 size  -> level index 0
        [0, 0, 1000, 1000],    # huge      -> clipped to coarsest (3)
    ], jnp.float32)
    out = jax.jit(pooler.forward)({}, feats, boxes)
    lvl = np.asarray(out)[:, 0, 0, 0]
    np.testing.assert_allclose(lvl, [2.0, 0.0, 3.0])


def test_assign_anchor_targets_matching_rules():
    """IoU thresholds, ignore band, force-positive best anchor per gt,
    padded-gt masking (reference: nn/AnchorTargetLayer.scala)."""
    from bigdl_tpu.nn.detection import assign_anchor_targets
    anchors = jnp.asarray(
        [[0, 0, 10, 10],          # exact match of gt0 (IoU 1.0) -> pos
         [0.5, 0.5, 10.5, 10.5],  # IoU 0.82 -> pos
         [40, 40, 50, 50],        # no overlap -> neg
         [2, 2, 14, 14]],         # IoU 0.36 -> ignore band
        jnp.float32)
    gt = jnp.asarray([[0, 0, 10, 10], [0, 0, 0, 0]], jnp.float32)
    valid = jnp.asarray([True, False])
    labels, targets = jax.jit(lambda a, g, v: assign_anchor_targets(
        a, g, v, pos_iou=0.7, neg_iou=0.3))(anchors, gt, valid)
    assert labels.tolist() == [1, 1, 0, -1]
    assert bool(jnp.isfinite(targets).all())
    np.testing.assert_allclose(np.asarray(targets[0]), [0, 0, 0, 0],
                               atol=1e-6)
    # no anchor clears pos_iou for a small gt: its best anchor is forced
    gt2 = jnp.asarray([[0, 0, 4, 4]], jnp.float32)
    labels2, _ = jax.jit(lambda a, g, v: assign_anchor_targets(
        a, g, v, pos_iou=0.9, neg_iou=0.0))(anchors, gt2,
                                            jnp.asarray([True]))
    assert int(labels2[0]) == 1


def test_rpn_loss_trains_toward_targets():
    """rpn_loss drives a free logits/deltas parameterization to the
    assigned labels: loss strictly decreases and positives' deltas
    approach the encode targets."""
    from bigdl_tpu.nn.detection import (Anchor, assign_anchor_targets,
                                        rpn_loss)
    anchor = Anchor(ratios=(1.0,), scales=(2.0,))
    anchors = anchor.generate(4, 4, 8)          # 16 anchors on a 32px image
    r = np.random.RandomState(0)
    gt = jnp.asarray([[[4, 4, 20, 20], [16, 12, 30, 28]]], jnp.float32)
    valid = jnp.asarray([[True, True]])

    logits = jnp.asarray(r.randn(1, 16).astype(np.float32))
    deltas = jnp.asarray(0.1 * r.randn(1, 16, 4).astype(np.float32))

    @jax.jit
    def step(lg, dl):
        (loss, _), (glg, gdl) = jax.value_and_grad(
            lambda a, b: rpn_loss(a, b, anchors, gt, valid,
                                  pos_iou=0.5, neg_iou=0.2),
            argnums=(0, 1), has_aux=True)(lg, dl)
        return lg - 0.5 * glg, dl - 0.5 * gdl, loss

    first = None
    for _ in range(400):
        logits, deltas, loss = step(logits, deltas)
        if first is None:
            first = float(loss)
    # BCE on free logits decays ~1/t once separable — 0.1x is the signal
    assert float(loss) < 0.1 * first
    labels, targets = assign_anchor_targets(anchors, gt[0], valid[0],
                                            pos_iou=0.5, neg_iou=0.2)
    pos = np.asarray(labels) == 1
    assert pos.any()
    np.testing.assert_allclose(np.asarray(deltas[0])[pos],
                               np.asarray(targets)[pos], atol=0.05)
    # positives score high, negatives low
    probs = 1 / (1 + np.exp(-np.asarray(logits[0])))
    assert probs[pos].min() > 0.8
    assert probs[np.asarray(labels) == 0].max() < 0.2


def test_force_positive_survives_padded_gt_rows():
    """Regression: padded gt columns argmax to anchor 0; their False
    writes must not clobber a valid gt's force-positive (OR-scatter)."""
    from bigdl_tpu.nn.detection import assign_anchor_targets
    anchors = jnp.asarray([[0, 0, 4, 4], [20, 20, 30, 30]], jnp.float32)
    gt = jnp.asarray([[0, 0, 2, 2], [0, 0, 0, 0]], jnp.float32)
    valid = jnp.asarray([True, False])
    labels, _ = jax.jit(lambda a, g, v: assign_anchor_targets(
        a, g, v, pos_iou=0.9, neg_iou=0.0))(anchors, gt, valid)
    # gt0's only overlapping anchor (index 0, the same index every padded
    # column argmaxes to) must stay force-positive
    assert int(labels[0]) == 1
