"""Independent numpy-reference oracles for the selection/post-processing
layers a finite-difference gradient check cannot cover (their outputs are
indices or NMS-selected slots). Each reference implementation below is a
from-scratch numpy rewrite of the textbook algorithm (greedy NMS, box
decode, bilinear RoI sampling) — not a call back into the library — so a
bug in the jit/lax formulation cannot cancel out (reference test strategy:
test/.../torch/*Spec.scala golden comparisons; here the oracle is numpy
instead of Torch7 for ops Torch7 does not expose).
"""

import numpy as np

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.detection import decode_boxes, encode_boxes, roi_align
from bigdl_tpu.nn.sparse import SparseCOO

R = np.random.RandomState(7)


# ------------------------------------------------------- numpy references
def np_greedy_nms(boxes, scores, iou_thr, max_out):
    """Textbook greedy NMS: pick highest score, drop overlaps, repeat."""
    boxes, scores = np.asarray(boxes, np.float64), np.asarray(scores,
                                                              np.float64)

    def iou(a, b):
        lt = np.maximum(a[:2], b[:2])
        rb = np.minimum(a[2:], b[2:])
        wh = np.maximum(rb - lt, 0)
        inter = wh[0] * wh[1]
        area = lambda q: max(q[2] - q[0], 0) * max(q[3] - q[1], 0)
        return inter / max(area(a) + area(b) - inter, 1e-9)

    alive = list(range(len(boxes)))
    kept = []
    while alive and len(kept) < max_out:
        best = max(alive, key=lambda i: scores[i])
        kept.append(best)
        alive = [i for i in alive
                 if i != best and iou(boxes[i], boxes[best]) <= iou_thr]
    return kept


def np_decode(anchors, deltas):
    anchors, deltas = np.asarray(anchors, np.float64), np.asarray(
        deltas, np.float64)
    aw = anchors[:, 2] - anchors[:, 0]
    ah = anchors[:, 3] - anchors[:, 1]
    ax = anchors[:, 0] + 0.5 * aw
    ay = anchors[:, 1] + 0.5 * ah
    cx = deltas[:, 0] * aw + ax
    cy = deltas[:, 1] * ah + ay
    w = np.exp(deltas[:, 2]) * aw
    h = np.exp(deltas[:, 3]) * ah
    return np.stack([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2], -1)


def np_roi_align(feat, box, out_hw, scale, sampling):
    """Per-bin average of bilinear samples — the RoiAlign paper's scheme
    with the standard Detectron conventions (continuous coordinate − 0.5
    pixel-center shift; box extent clamped to ≥ 1 px), written directly
    from the definition."""
    feat = np.asarray(feat, np.float64)        # (H, W, C)
    H, W, C = feat.shape
    x1, y1, x2, y2 = [v * scale for v in np.asarray(box, np.float64)]
    oh, ow = out_hw
    bh, bw = max(y2 - y1, 1.0) / oh, max(x2 - x1, 1.0) / ow
    out = np.zeros((oh, ow, C))

    def bilinear(y, x):
        y = min(max(y, 0.0), H - 1)
        x = min(max(x, 0.0), W - 1)
        y0, x0 = int(np.floor(y)), int(np.floor(x))
        y1_, x1_ = min(y0 + 1, H - 1), min(x0 + 1, W - 1)
        ly, lx = y - y0, x - x0
        return (feat[y0, x0] * (1 - ly) * (1 - lx)
                + feat[y0, x1_] * (1 - ly) * lx
                + feat[y1_, x0] * ly * (1 - lx)
                + feat[y1_, x1_] * ly * lx)

    for i in range(oh):
        for j in range(ow):
            acc = np.zeros(C)
            for si in range(sampling):
                for sj in range(sampling):
                    yy = y1 + bh * (i + (si + 0.5) / sampling) - 0.5
                    xx = x1 + bw * (j + (sj + 0.5) / sampling) - 0.5
                    acc += bilinear(yy, xx)
            out[i, j] = acc / (sampling * sampling)
    return out


# ----------------------------------------------------------------- tests
def test_nms_matches_numpy_greedy():
    boxes = np.abs(R.randn(24, 2)) * 30
    boxes = np.concatenate([boxes, boxes + 8 + np.abs(R.randn(24, 2)) * 25],
                           axis=1).astype(np.float32)
    scores = R.rand(24).astype(np.float32)

    layer = nn.Nms(iou_threshold=0.45, max_output=10)
    idx, valid = layer.forward({}, jnp.asarray(boxes), jnp.asarray(scores))
    got = list(np.asarray(idx)[np.asarray(valid)])
    want = np_greedy_nms(boxes, scores, 0.45, 10)
    assert got == want, (got, want)


def test_nms_under_jit_matches_numpy():
    boxes = np.abs(R.randn(16, 2)) * 20
    boxes = np.concatenate([boxes, boxes + 5 + np.abs(R.randn(16, 2)) * 15],
                           axis=1).astype(np.float32)
    scores = R.rand(16).astype(np.float32)
    layer = nn.Nms(iou_threshold=0.5, max_output=8)
    idx, valid = jax.jit(lambda b, s: layer.forward({}, b, s))(
        jnp.asarray(boxes), jnp.asarray(scores))
    got = list(np.asarray(idx)[np.asarray(valid)])
    assert got == np_greedy_nms(boxes, scores, 0.5, 8)


def test_box_decode_encode_match_numpy():
    anchors = np.abs(R.randn(12, 2)) * 20
    anchors = np.concatenate([anchors, anchors + 4 + np.abs(R.randn(12, 2))
                              * 20], 1).astype(np.float32)
    deltas = (R.randn(12, 4) * 0.2).astype(np.float32)
    got = np.asarray(decode_boxes(jnp.asarray(anchors),
                                  jnp.asarray(deltas)))
    np.testing.assert_allclose(got, np_decode(anchors, deltas), rtol=1e-4)
    # encode is the exact inverse
    back = np.asarray(encode_boxes(jnp.asarray(anchors), jnp.asarray(got)))
    np.testing.assert_allclose(back, deltas, rtol=1e-3, atol=1e-5)


def test_roi_align_matches_numpy_bilinear():
    feat = R.randn(1, 9, 9, 3).astype(np.float32)
    boxes = np.asarray([[2.0, 1.0, 14.0, 13.0], [0.0, 0.0, 8.0, 6.0]],
                       np.float32)
    layer = nn.RoiAlign((3, 3), spatial_scale=0.5, sampling_ratio=2)
    # (jitted, as the two pipelines below: one program, where the eager
    # call compiles each op of the sampling grid or the NMS loop on its own)
    got = np.asarray(jax.jit(layer.forward)(
        {}, jnp.asarray(feat), jnp.asarray(boxes),
        jnp.zeros((2,), jnp.int32)))
    for k in range(2):
        want = np_roi_align(feat[0], boxes[k], (3, 3), 0.5, 2)
        np.testing.assert_allclose(got[k], want, rtol=1e-4, atol=1e-5)


def test_detection_output_ssd_matches_numpy_pipeline():
    """SSD head = decode → background drop → per-class NMS → top-k; rebuild
    that pipeline in numpy from the primitives verified above."""
    priors = np.abs(R.randn(10, 2)) * 20
    priors = np.concatenate([priors, priors + 6 + np.abs(R.randn(10, 2))
                             * 20], 1).astype(np.float32)
    loc = (R.randn(10, 4) * 0.1).astype(np.float32)
    conf = R.rand(10, 3).astype(np.float32)
    conf /= conf.sum(1, keepdims=True)

    head = nn.DetectionOutputSSD(n_classes=3, iou_threshold=0.45, top_k=5,
                                 conf_threshold=0.01, background_id=0)
    boxes, scores, valid = jax.jit(head.forward)(
        {}, jnp.asarray(priors), jnp.asarray(loc), jnp.asarray(conf))
    decoded = np_decode(priors, loc)
    for cls in (1, 2):                       # non-background classes
        s = conf[:, cls].copy()
        s[s < 0.01] = 0.0
        keep = np_greedy_nms(decoded, s, 0.45, 5)
        keep = [i for i in keep if s[i] > 0][:5]
        got_boxes = np.asarray(boxes[cls])[np.asarray(valid[cls])]
        got_scores = np.asarray(scores[cls])[np.asarray(valid[cls])]
        np.testing.assert_allclose(got_boxes, decoded[keep], rtol=1e-4)
        np.testing.assert_allclose(got_scores, s[keep], rtol=1e-5)


def np_conv2d(x, w, b, stride=1, pad=0):
    """Direct-loop NHWC conv (independent of lax.conv)."""
    x = np.asarray(x, np.float64)
    w = np.asarray(w, np.float64)
    if pad:
        x = np.pad(x, [(0, 0), (pad, pad), (pad, pad), (0, 0)])
    B, H, W, Ci = x.shape
    kh, kw, _, Co = w.shape
    oh, ow = (H - kh) // stride + 1, (W - kw) // stride + 1
    out = np.zeros((B, oh, ow, Co))
    for i in range(oh):
        for j in range(ow):
            patch = x[:, i * stride:i * stride + kh,
                      j * stride:j * stride + kw, :]
            out[:, i, j, :] = np.tensordot(patch, w, axes=([1, 2, 3],
                                                           [0, 1, 2]))
    return out + np.asarray(b, np.float64)


def test_region_proposal_matches_numpy_pipeline():
    """Full RPN oracle: conv head → anchors → decode → clip → sigmoid →
    greedy NMS, every stage re-derived in numpy (reference:
    nn/RegionProposal.scala:40-247). NMS selection is not finite-
    differenceable, so this end-to-end golden is RegionProposal's numeric
    oracle."""
    rp = nn.RegionProposal(in_channels=4, anchor_sizes=(16,),
                           aspect_ratios=(0.5, 1.0, 2.0),
                           anchor_stride=(8,), pre_nms_top_n=200,
                           post_nms_top_n=6, nms_thresh=0.6, min_size=0)
    params, state = rp.init(jax.random.PRNGKey(5))
    feat = R.randn(1, 8, 8, 4).astype(np.float32) * 2.0
    (props, valid), _ = jax.jit(
        lambda p, s, f: rp.apply(p, s, f, (64, 64)))(
            params, state, (jnp.asarray(feat),))

    # --- numpy re-derivation
    p = jax.tree.map(np.asarray, params)
    h = np.maximum(np_conv2d(feat, p["conv"]["weight"], p["conv"]["bias"],
                             pad=1), 0.0)
    logits = np_conv2d(h, p["cls_logits"]["weight"],
                       p["cls_logits"]["bias"])
    deltas = np_conv2d(h, p["bbox_pred"]["weight"], p["bbox_pred"]["bias"])
    na = 3
    scores = logits.reshape(-1)                       # (8*8*3,)
    deltas = deltas.reshape(-1, 4)
    # anchors: ratios-major base boxes at (cell+0.5)*stride centers
    base = []
    for r in (0.5, 1.0, 2.0):
        size = (16.0 / 8.0) * 8           # scale(size/stride) * stride
        w_, h_ = size * np.sqrt(1 / r), size * np.sqrt(r)
        base.append([-w_ / 2, -h_ / 2, w_ / 2, h_ / 2])
    anchors = []
    for yy in range(8):
        for xx in range(8):
            cx, cy = (xx + 0.5) * 8, (yy + 0.5) * 8
            for bb in base:
                anchors.append([cx + bb[0], cy + bb[1],
                                cx + bb[2], cy + bb[3]])
    anchors = np.asarray(anchors)
    boxes = np_decode(anchors, deltas)
    boxes[:, 0] = boxes[:, 0].clip(0, 64)
    boxes[:, 1] = boxes[:, 1].clip(0, 64)
    boxes[:, 2] = boxes[:, 2].clip(0, 64)
    boxes[:, 3] = boxes[:, 3].clip(0, 64)
    sig = 1.0 / (1.0 + np.exp(-scores))
    keep = np_greedy_nms(boxes, sig, 0.6, 6)

    got = np.asarray(props[0])[np.asarray(valid[0])]
    np.testing.assert_allclose(got, boxes[keep], rtol=1e-3, atol=1e-3)


def test_sparse_join_table_matches_dense_concat():
    """SparseJoinTable's oracle: densify(join(a, b)) must equal
    np.concatenate(densify(a), densify(b)) — exact, including pad
    collisions after the id shift."""
    r = np.random.RandomState(23)
    da = r.rand(4, 9).astype(np.float32)
    da[da < 0.6] = 0.0
    db = r.rand(4, 7).astype(np.float32)
    db[db < 0.6] = 0.0
    sa = SparseCOO.from_dense(da, nnz_per_row=9)
    sb = SparseCOO.from_dense(db, nnz_per_row=7)
    joined = nn.SparseJoinTable().forward({}, sa, sb)
    np.testing.assert_allclose(np.asarray(joined.to_dense()),
                               np.concatenate([da, db], axis=1), rtol=1e-6)
    assert joined.n_cols == 16


def test_lookup_table_sparse_matches_dense_embedding_sum():
    """Sparse embedding-bag vs the dense formulation: sum_i v_i * E[id_i]
    == to_dense(x) @ E."""
    d = R.rand(3, 12).astype(np.float32)
    d[d < 0.7] = 0.0
    sp = SparseCOO.from_dense(d, nnz_per_row=4)
    dense = np.asarray(sp.to_dense())   # truncation applied, if any
    layer = nn.LookupTableSparse(12, 6, combiner="sum")
    params, state = layer.init(jax.random.PRNGKey(3))
    got = np.asarray(layer.forward(params, sp))
    table = np.asarray(jax.tree.leaves(params)[0])
    np.testing.assert_allclose(got, dense @ table, rtol=1e-4, atol=1e-5)
