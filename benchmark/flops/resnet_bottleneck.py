"""Model FLOPs of one training image of a bottleneck ResNet (He et al. 2015,
table 1), from the configuration's shapes alone.

Walks the layers as `paddle_tpu/models/resnet.py` builds them (stride in the
first 1x1 convolution of a stage's first block, projection shortcut where
the channels change). Counted: convolutions and the final fully connected
layer, 2 FLOPs per multiply-add, backward as twice the forward. Not counted:
batch norm, ReLU, pooling, softmax, the optimizer.
"""

_STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def _conv(h, w, c_in, c_out, k, stride, pad):
    ho = (h + 2 * pad - k) // stride + 1
    wo = (w + 2 * pad - k) // stride + 1
    return ho, wo, ho * wo * c_in * c_out * k * k


def flops_per_example(depth=50, class_dim=1000, image_shape=(3, 224, 224),
                      **_):
    c, h, w = image_shape
    macs = 0
    h, w, m = _conv(h, w, c, 64, 7, 2, 3)
    macs += m
    h, w = (h + 2 - 3) // 2 + 1, (w + 2 - 3) // 2 + 1     # 3x3/2 max pool
    c = 64
    for stage, count in enumerate(_STAGES[depth]):
        ch = 64 * 2 ** stage
        for block in range(count):
            stride = 2 if (block == 0 and stage > 0) else 1
            if c != 4 * ch:
                macs += _conv(h, w, c, 4 * ch, 1, stride, 0)[2]
            h, w, m = _conv(h, w, c, ch, 1, stride, 0)
            macs += m
            macs += _conv(h, w, ch, ch, 3, 1, 1)[2]
            macs += _conv(h, w, ch, 4 * ch, 1, 1, 0)[2]
            c = 4 * ch
    macs += c * class_dim
    fwd = 2 * macs
    return {"forward": fwd, "forward_backward": 3 * fwd}
