"""ResNet-50 for ImageNet as upstream MXNet's
example/image-classification/symbols/resnet.py builds it (bottleneck
units 3-4-6-3 of He et al., arXiv:1512.03385 Table 1, in the
pre-activation arrangement of that file): the symbol for ``Module``, the
weights from a seed, and what one training step requires of the chip.

Only ``build_symbol`` touches the program; the rest is shapes and ``jax``.
"""
import math

UNITS = {50: (3, 4, 6, 3)}
FILTERS = (64, 256, 512, 1024, 2048)


def layers(cfg):
    """Every weighted layer in graph order:
    (kind, name, cin, cout, kernel, stride, input_hw, output_hw)."""
    hw = cfg["image_size"]
    out = [("bn", "bn_data", 3, 3, 0, 1, hw, hw),
           ("conv", "conv0", 3, FILTERS[0], 7, 2, hw, hw // 2),
           ("bn", "bn0", FILTERS[0], FILTERS[0], 0, 1, hw // 2, hw // 2)]
    hw //= 4                              # stride-2 stem, stride-2 pool
    cin = FILTERS[0]
    for s, n_units in enumerate(UNITS[cfg["num_layers"]]):
        cout = FILTERS[s + 1]
        mid = cout // 4
        for u in range(n_units):
            name = "stage%d_unit%d" % (s + 1, u + 1)
            stride = 2 if (u == 0 and s > 0) else 1
            ohw = hw // stride
            out += [("bn", name + "_bn1", cin, cin, 0, 1, hw, hw),
                    ("conv", name + "_conv1", cin, mid, 1, 1, hw, hw),
                    ("bn", name + "_bn2", mid, mid, 0, 1, hw, hw),
                    ("conv", name + "_conv2", mid, mid, 3, stride, hw, ohw),
                    ("bn", name + "_bn3", mid, mid, 0, 1, ohw, ohw),
                    ("conv", name + "_conv3", mid, cout, 1, 1, ohw, ohw)]
            if u == 0:
                out.append(("conv", name + "_sc", cin, cout, 1, stride,
                            hw, ohw))
            cin, hw = cout, ohw
    out += [("bn", "bn1", cin, cin, 0, 1, hw, hw),
            ("fc", "fc1", cin, cfg["num_classes"], 1, 1, 1, 1)]
    return out


def param_shapes(cfg):
    """(arguments, auxiliary states) by name; convolution weights are
    (out, kh, kw, in), the channels-last layout of ``layout='NHWC'``."""
    args, aux = {}, {}
    for kind, name, cin, cout, k, _s, _i, _o in layers(cfg):
        if kind == "bn":
            args[name + "_gamma"] = (cin,)
            args[name + "_beta"] = (cin,)
            aux[name + "_moving_mean"] = (cin,)
            aux[name + "_moving_var"] = (cin,)
        elif kind == "conv":
            args[name + "_weight"] = (cout, k, k, cin)
        else:
            args[name + "_weight"] = (cout, cin)
            args[name + "_bias"] = (cout,)
    return args, aux


def param_count(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg)[0].values())


def build_symbol(cfg):
    from mxnet_tpu.models import get_resnet_symbol
    hw = cfg["image_size"]
    return get_resnet_symbol(num_classes=cfg["num_classes"],
                             num_layers=cfg["num_layers"],
                             image_shape=(3, hw, hw), layout="NHWC")


def _key(seed, stream):
    import jax
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                             seed // (2 ** 31))
    return jax.random.fold_in(key, stream)


def init_params(cfg, seed):
    """(arguments, auxiliary states) on the default device in one jitted
    call: weights normal at sqrt(2 / fan_in) (``mx.init.Xavier(
    rnd_type='gaussian', factor_type='in', magnitude=2)``), gamma 1,
    beta and bias 0, moving mean 0 and variance 1."""
    import jax
    import jax.numpy as jnp
    args, aux = param_shapes(cfg)
    names = sorted(args)

    def make(key):
        out = {}
        for k, name in zip(jax.random.split(key, len(names)), names):
            shape = args[name]
            if name.endswith("_weight"):
                out[name] = math.sqrt(2.0 / math.prod(shape[1:])) \
                    * jax.random.normal(k, shape, jnp.float32)
            elif name.endswith("_gamma"):
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = jnp.zeros(shape, jnp.float32)
        auxs = {n: (jnp.ones if n.endswith("_var") else jnp.zeros)(
            s, jnp.float32) for n, s in aux.items()}
        return out, auxs

    return jax.jit(make)(_key(seed, 0))


def input_descs(cfg, batch):
    """(name, shape) of the data and of the label one step takes."""
    hw = cfg["image_size"]
    return [("data", (batch, hw, hw, 3)), ("softmax_label", (batch,))]


def make_batch(cfg, seed, batch, sharding=None):
    """One batch of images (standard normal, NHWC) and labels (float32
    class ids, as ``Module`` takes them), made on the device; under
    ``sharding`` each chip makes and keeps its own rows."""
    import jax
    import jax.numpy as jnp
    (_d, dshape), (_l, lshape) = input_descs(cfg, batch)

    def make(key):
        kd, kl = jax.random.split(key)
        data = jax.random.normal(kd, dshape, jnp.float32)
        label = jax.random.randint(kl, lshape, 0, cfg["num_classes"])
        return data, label.astype(jnp.float32)

    return jax.jit(make, out_shardings=sharding)(_key(seed, 1))


def step_required(cfg, batch):
    """FLOPs and HBM bytes one training step over ``batch`` images needs.

    FLOPs: 2 per multiply-add of every convolution and the classifier,
    forward, and twice that backward (gradient to the input and to the
    weight; conv0's input gradient feeds bn_data's beta).

    Bytes: float32, and only what no schedule can avoid: each
    convolution and the classifier reads its input and writes its output
    forward, and backward reads the output's gradient and the input
    again and writes the input's gradient (3 inputs + 2 outputs); each
    weight is read twice, and its gradient, momentum and new value are
    written or read once each (6 accesses).  Batch norm and ReLU are
    taken to ride in the convolutions' prologues and epilogues, so this
    is a lower estimate of the traffic and the share it gives is a lower
    one: it cannot pass 100 % by miscounting."""
    item = 4
    fwd = 0.0
    act = 0.0
    for kind, _name, cin, cout, k, _s, ihw, ohw in layers(cfg):
        if kind == "bn":
            continue
        fwd += 2.0 * batch * ohw * ohw * cout * cin * k * k
        act += batch * (3.0 * ihw * ihw * cin + 2.0 * ohw * ohw * cout)
    flops = 3.0 * fwd
    nbytes = item * (act + 6.0 * param_count(cfg))
    return {"flops": flops, "forward_flops": fwd, "bytes": nbytes}
