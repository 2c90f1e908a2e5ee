"""ResNet-50 v1 (He et al., arXiv:1512.03385, Table 1, 50-layer column).

Bottleneck blocks [3, 4, 6, 3] at output widths 256/512/1024/2048, 7x7/2
stem, 3x3/2 max pool, global average pool, 1000-way linear head. NHWC.
Departures from the paper, as the configuration file states them: the
stride-2 3x3 convolution carries the down-sampling (the "v1.5" placement
every public implementation uses), the last BatchNorm scale of each block
starts at zero (Goyal et al., arXiv:1706.02677, section 5.1), convolutions
and BatchNorm outputs are computed in ``dtype`` with float32 parameters and
float32 BatchNorm statistics, input pixels arrive as uint8 and are
normalised with the ImageNet channel mean and deviation on the device.

Sub-module class names fix the parameter names (``BottleneckBlock_3/Conv_0``)
and with them the order of the flat parameter vector the comparison reads.
"""

import functools

import flax.linen as nn
import jax.numpy as jnp
import optax

MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)


class BottleneckBlock(nn.Module):
    filters: int
    strides: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, train):
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = functools.partial(nn.BatchNorm, use_running_average=not train,
                                 dtype=self.dtype)
        inner = self.filters // 4
        y = nn.relu(norm()(conv(inner, (1, 1))(x)))
        y = conv(inner, (3, 3), strides=self.strides, padding=1)(y)
        y = nn.relu(norm()(y))
        y = norm(scale_init=nn.initializers.zeros)(conv(self.filters, (1, 1))(y))
        if x.shape[-1] != self.filters or self.strides != 1:
            x = norm()(conv(self.filters, (1, 1), strides=self.strides)(x))
        return nn.relu(x + y)


class ResNetV1(nn.Module):
    stage_sizes: tuple
    num_classes: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, train):
        x = nn.Conv(64, (7, 7), strides=2, padding=3, use_bias=False,
                    dtype=self.dtype)(x)
        x = nn.BatchNorm(use_running_average=not train, dtype=self.dtype)(x)
        x = nn.max_pool(nn.relu(x), (3, 3), strides=(2, 2),
                        padding=((1, 1), (1, 1)))
        for stage, blocks in enumerate(self.stage_sizes):
            for block in range(blocks):
                x = BottleneckBlock(256 * 2 ** stage,
                                    2 if stage > 0 and block == 0 else 1,
                                    self.dtype)(x, train)
        x = nn.Dense(self.num_classes, dtype=self.dtype)(jnp.mean(x, (1, 2)))
        return x.astype(jnp.float32)


def build(sizes, dtype):
    module = ResNetV1(tuple(sizes["stage_sizes"]), sizes["num_classes"], dtype)
    s = sizes["image_size"]
    return module, jnp.zeros((1, s, s, 3), jnp.uint8)


def initial_carry(sizes, batch, dtype):
    return ()


def loss(module, variables, carry, batch, key, train):
    x = (batch["image"].astype(jnp.float32) / 255.0 - jnp.asarray(MEAN)
         ) / jnp.asarray(STD)
    if train:
        logits, mut = module.apply(variables, x, True, mutable=["batch_stats"])
        model_state = mut["batch_stats"]
    else:
        logits, model_state = module.apply(variables, x, False), None
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits, batch["label"]).mean()
    return ce, model_state, carry


def forward_macs(sizes):
    """Multiply-accumulates of one image's forward pass, from the shapes:
    convolutions and the linear head (BatchNorm, ReLU, pooling and the
    input's normalisation are not counted)."""
    side = sizes["image_size"] // 2                  # 7x7 stride 2
    macs = side * side * 7 * 7 * 3 * 64
    side //= 2                                        # 3x3 max pool stride 2
    width_in = 64
    for stage, blocks in enumerate(sizes["stage_sizes"]):
        out = 256 * 2 ** stage
        inner = out // 4
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            macs += side * side * width_in * inner              # 1x1
            after = side // stride
            macs += after * after * 9 * inner * inner           # 3x3, strided
            macs += after * after * inner * out                 # 1x1
            if width_in != out or stride != 1:
                macs += after * after * width_in * out          # projection
            side, width_in = after, out
    return macs + width_in * sizes["num_classes"]
