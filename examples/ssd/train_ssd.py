"""Tiny SSD-style detector, trained end to end (ref: example/ssd — the
reference's headline detection example over the MultiBox op family).

TPU-native shape: a small Gluon conv backbone emits TWO feature scales;
each scale gets anchors (`mx.nd.multibox_prior`), a class head, and a box
head. Training targets come from `mx.nd.multibox_target` (matching +
offset encoding), the loss is softmax CE (classes) + masked L1 (offsets),
and inference decodes + NMS-es with `mx.nd.multibox_detection` — the
same three-op pipeline as the reference's symbol graph
(src/operator/contrib/multibox_*.cc), here driven imperatively under
autograd and hybridizable like any Gluon net.

Synthetic task: one axis-aligned bright rectangle per 64x64 image;
class 0 = "box". Run: python examples/ssd/train_ssd.py
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxtpu as mx  # noqa: E402
from mxtpu import autograd, gluon  # noqa: E402
from mxtpu.gluon import nn  # noqa: E402


def make_synthetic(num, size=64, seed=0):
    """Images with one bright rectangle; labels (num, 1, 5) as
    [cls, xmin, ymin, xmax, ymax] in [0, 1] (the MultiBoxTarget format)."""
    r = np.random.RandomState(seed)
    imgs = r.uniform(0, 0.2, (num, size, size, 3)).astype(np.float32)
    labels = np.zeros((num, 1, 5), np.float32)
    for i in range(num):
        w, h = r.randint(size // 4, size // 2, 2)
        x0 = r.randint(0, size - w)
        y0 = r.randint(0, size - h)
        imgs[i, y0:y0 + h, x0:x0 + w] += 0.8
        labels[i, 0] = [0, x0 / size, y0 / size, (x0 + w) / size,
                        (y0 + h) / size]
    return imgs.clip(0, 1), labels


class TinySSD(gluon.HybridBlock):
    """Two-scale SSD head over a 3-block backbone. num_anchors per pixel
    is len(sizes) + len(ratios) - 1 (the multibox_prior convention)."""

    SIZES = ([0.3, 0.45], [0.6, 0.8])
    RATIOS = ([1.0, 2.0, 0.5],) * 2
    NUM_CLASSES = 1

    def __init__(self, **kw):
        super().__init__(**kw)
        na = len(self.SIZES[0]) + len(self.RATIOS[0]) - 1
        self._na = na
        with self.name_scope():
            self.backbone = nn.HybridSequential()
            for ch in (16, 32):
                self.backbone.add(nn.Conv2D(ch, 3, padding=1),
                                  nn.BatchNorm(),
                                  nn.Activation("relu"),
                                  nn.MaxPool2D(2))
            self.scale2 = nn.HybridSequential()
            self.scale2.add(nn.Conv2D(64, 3, strides=2, padding=1),
                            nn.BatchNorm(), nn.Activation("relu"))
            # per-scale heads: (classes+1) and 4 offsets per anchor
            self.cls1 = nn.Conv2D(na * (self.NUM_CLASSES + 1), 3, padding=1)
            self.box1 = nn.Conv2D(na * 4, 3, padding=1)
            self.cls2 = nn.Conv2D(na * (self.NUM_CLASSES + 1), 3, padding=1)
            self.box2 = nn.Conv2D(na * 4, 3, padding=1)

    def hybrid_forward(self, F, x):
        f1 = self.backbone(x)                 # size/4
        f2 = self.scale2(f1)                  # size/8
        c = self.NUM_CLASSES + 1
        outs = []
        for feat, cls_head, box_head in ((f1, self.cls1, self.box1),
                                         (f2, self.cls2, self.box2)):
            cp = cls_head(feat)               # NCHW [B, na*c, H, W]
            bp = box_head(feat)
            b = cp.shape[0]
            hw = cp.shape[2] * cp.shape[3]
            cp = cp.reshape((b, self._na, c, hw)).transpose(
                (0, 3, 1, 2)).reshape((b, hw * self._na, c))
            bp = bp.reshape((b, self._na * 4, hw)).transpose(
                (0, 2, 1)).reshape((b, hw * self._na * 4))
            outs.append((cp, bp))
        cls_preds = mx.nd.concat(outs[0][0], outs[1][0], dim=1)
        loc_preds = mx.nd.concat(outs[0][1], outs[1][1], dim=1)
        return cls_preds, loc_preds

    def anchors(self, x):
        """Per-scale multibox priors, concatenated [1, A, 4]."""
        f1_hw = x.shape[1] // 4
        f2_hw = x.shape[1] // 8
        ank = []
        for hw, sizes, ratios in ((f1_hw, self.SIZES[0], self.RATIOS[0]),
                                  (f2_hw, self.SIZES[1], self.RATIOS[1])):
            feat = mx.nd.zeros((1, 1, hw, hw))
            ank.append(mx.nd.multibox_prior(feat, sizes=sizes,
                                            ratios=ratios))
        return mx.nd.concat(*ank, dim=1)


def train(num_images=32, batch_size=8, epochs=12, lr=0.05, seed=0):
    imgs, labels = make_synthetic(num_images, seed=seed)
    net = TinySSD()
    net.initialize()
    # NCHW input for the conv heads
    x_all = mx.nd.array(imgs.transpose(0, 3, 1, 2))
    y_all = mx.nd.array(labels)
    anchors = net.anchors(mx.nd.array(imgs))     # [1, A, 4]

    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": lr})
    cls_loss = gluon.loss.SoftmaxCrossEntropyLoss()
    box_loss = gluon.loss.L1Loss()

    hist = []
    for ep in range(epochs):
        total = 0.0
        for s in range(0, num_images, batch_size):
            xb = x_all[s:s + batch_size]
            yb = y_all[s:s + batch_size]
            with autograd.record():
                cls_preds, loc_preds = net(xb)
                # targets are CONSTANTS (matching + offset encoding is
                # non-differentiable, ref multibox_target.cc FGradient
                # none): pause recording so the target op stays OFF the
                # tape, and detach the predictions it matches against
                with autograd.pause():
                    loc_t, loc_m, cls_t = mx.nd.multibox_target(
                        anchors, yb,
                        cls_preds.detach().transpose((0, 2, 1)))
                l_cls = cls_loss(
                    cls_preds.reshape((-1, net.NUM_CLASSES + 1)),
                    cls_t.reshape((-1,)))
                l_box = box_loss(loc_preds * loc_m, loc_t * loc_m)
                loss = l_cls.mean() + l_box.mean()
            loss.backward()
            # mean losses => step(1): Trainer.step's rescale_grad is
            # 1/batch, and mean+step(batch) would divide twice, silently
            # coupling the learning rate to the batch size
            trainer.step(1)
            total += float(loss.asnumpy())
        hist.append(total / max(1, num_images // batch_size))
    return net, anchors, hist


def detect(net, anchors, imgs_nhwc):
    """[B, A, 6] rows of [cls_id, score, xmin, ymin, xmax, ymax]."""
    x = mx.nd.array(np.asarray(imgs_nhwc).transpose(0, 3, 1, 2))
    cls_preds, loc_preds = net(x)
    cls_prob = mx.nd.softmax(cls_preds, axis=-1).transpose((0, 2, 1))
    return mx.nd.multibox_detection(cls_prob, loc_preds, anchors,
                                    nms_threshold=0.45)


def main():
    net, anchors, hist = train()
    print("loss: %.3f -> %.3f" % (hist[0], hist[-1]))
    imgs, labels = make_synthetic(4, seed=123)
    det = detect(net, anchors, imgs).asnumpy()
    for i in range(det.shape[0]):
        rows = det[i]
        best = rows[rows[:, 0] >= 0]
        if len(best):
            b = best[np.argmax(best[:, 1])]
            print("img %d: cls=%d score=%.2f box=[%.2f %.2f %.2f %.2f] "
                  "gt=%s" % (i, int(b[0]), b[1], *b[2:6],
                             np.round(labels[i, 0, 1:], 2)))


if __name__ == "__main__":
    from mxtpu import compile_service
    compile_service.use_checkout_xla_cache()
    main()
