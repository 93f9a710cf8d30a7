"""The served fold kernels compile for a TPU v5e.

Each test lowers one fold kernel launch of the served models at their
published widths (224 px, width 1.0, batch bucket 8) and compiles it for
a v5e chip that is *described*, not attached
(``jax.experimental.topologies``): the TPU compiler refuses here what it
would refuse on the chip — an illegal block tiling, a primitive Mosaic
cannot lower, a kernel that needs more VMEM than it asked for.  The
interpreter accepts all of these, so these tests are the guard for the
real lowering.

The kernels are called directly with ``interpret=False`` on shape-only
arguments: code that asks ``jax.default_backend()`` sees the CPU here and
would pick interpret mode.  Schedules come from the engine's
``ScheduleCache`` as on the served path, and each test checks that the
VMEM the compiled kernel was granted is exactly what foldlint's figure
(``conv_working_set``: the blocks the kernel really holds) asks for.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.plan_check import check_plan
from repro.core.engine import ScheduleCache
from repro.core.epilogue import Epilogue
from repro.core.loopnest import ConvLoopNest
from repro.core.mapping import conv_working_set, vmem_request_bytes
from repro.core.quant import requant_epilogue
from repro.kernels.conv2d_ws import (conv2d_folded, fold_kernel_spec,
                                     kernel_name)

BATCH = 8                       # the largest default serving bucket

_RELU = Epilogue(bias=True, relu=True)
_POOL = Epilogue(bias=True, relu=True, pool="max2")

# (input channels, filters, input height = width, stride, groups, epilogue)
# of every distinct VGG-16 conv launch at 224 px (conv5_2 == conv5_1):
# eight fold schedules, each with the epilogue and spatial size it serves
VGG16 = {
    "conv1_1": (3, 64, 224, 1, 1, _RELU),
    "conv1_2": (64, 64, 224, 1, 1, _POOL),
    "conv2_1": (64, 128, 112, 1, 1, _RELU),
    "conv2_2": (128, 128, 112, 1, 1, _POOL),
    "conv3_1": (128, 256, 56, 1, 1, _RELU),
    "conv3_2": (256, 256, 56, 1, 1, _RELU),
    "conv3_3": (256, 256, 56, 1, 1, _POOL),
    "conv4_1": (256, 512, 28, 1, 1, _RELU),
    "conv4_2": (512, 512, 28, 1, 1, _RELU),
    "conv4_3": (512, 512, 28, 1, 1, _POOL),
    "conv5_1": (512, 512, 14, 1, 1, _RELU),
    "conv5_3": (512, 512, 14, 1, 1, _POOL),
}

# the other dataflows ``policy="auto"`` sends to the same kernels on TPU
OTHERS = {
    "resnet18.s3b0_c1": (128, 256, 112, 2, 1, _RELU),          # stride 2
    "mobilenetv2.b7_dw": (384, 384, 56, 1, 384,                 # depthwise
                          Epilogue(scale=True, relu6=True)),
}

_RESIDUAL = Epilogue(bias=True, relu=True, residual=True)

# (input channels, filters, input height = width, stride, groups,
# epilogue) of ResNet-18's (CIFAR, 32 px) stride-1 3x3 launches of stages
# 2-4 at its served bucket of 256, whose output rows pack k to a dot
RESNET18_BUCKET = 256
RESNET18_PACKED = {
    "s2_c1": (128, 128, 16, 1, 1, _RELU),
    "s2_c2": (128, 128, 16, 1, 1, _RESIDUAL),
    "s3_c1": (256, 256, 8, 1, 1, _RELU),
    "s3_c2": (256, 256, 8, 1, 1, _RESIDUAL),
    "s4_c1": (512, 512, 4, 1, 1, _RELU),
    "s4_c2": (512, 512, 4, 1, 1, _RESIDUAL),
}

_BN_RELU6 = Epilogue(scale=True, relu6=True)
_BN = Epilogue(scale=True)

# (input channels, filters, input height = width, stride, groups,
# epilogue, kernel size) of MobileNetV2-224's distinct launch shapes
# (Table 2, width 1.0), at its served bucket of 64
MOBILENETV2_BUCKET = 64
MOBILENETV2_224 = {
    "stem": (3, 32, 224, 2, 1, _BN_RELU6, 3),
    "b1_exp": (16, 96, 112, 1, 1, _BN_RELU6, 1),
    "b1_dw": (96, 96, 112, 2, 96, _BN_RELU6, 3),
    "b13_dw": (576, 576, 14, 2, 576, _BN_RELU6, 3),
    "b14_dw": (960, 960, 7, 1, 960, _BN_RELU6, 3),
    "b16_proj": (960, 320, 7, 1, 1, _BN, 1),
    "head": (320, 1280, 7, 1, 1, _BN_RELU6, 1),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip.  The persistent compilation cache is off
    while these compile: an entry written for the described chip cannot
    be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _granted_vmem(hlo: str):
    """The scoped-VMEM size each compiled Mosaic kernel was granted."""
    return [int(m) for m in re.findall(
        r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"0",'
        r'"size":"(\d+)"\}\],"custom_call_config"', hlo)]


def _compile_launch(one_chip, c, nf, hw, stride, groups, epi, k=3,
                    precision="fp32", batch=BATCH):
    cv = ConvLoopNest(n=batch, nf=nf, c=c, r=k, s=k, x=hw, y=hw,
                      stride=stride, pad=k // 2, groups=groups)
    sched = ScheduleCache().schedule_for(cv, precision=precision)
    plan = sched.plan.clamped(cv.nf, cv.c, cv.p)
    dtype, width = ((jnp.int8, 1) if precision == "int8"
                    else (jnp.float32, 4))
    if precision == "int8":
        epi = requant_epilogue(epi)

    def shape(dims, dt=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)
    x = shape((cv.n, cv.c, cv.padded_x, cv.padded_y), dtype)
    w = shape((cv.nf, cv.cg, cv.r, cv.s), dtype)
    vecs = {}
    if epi.bias:
        vecs["bias"] = shape((cv.nf,))
    if epi.scale:
        vecs["scale"] = vecs["shift"] = shape((cv.nf,))
    if epi.residual:
        vecs["residual"] = shape((cv.n, cv.nf, cv.p, cv.q))

    def launch(x, w, vecs):
        return conv2d_folded(x, w, stride=stride, plan=plan,
                             dataflow=sched.dataflow, interpret=False,
                             epilogue=epi, groups=groups, **vecs)
    hlo = jax.jit(launch).lower(x, w, vecs).compile().as_text()
    assert "tpu_custom_call" in hlo
    # the launch's stable name reaches the compiled Mosaic custom call
    spec = fold_kernel_spec(x.shape, w.shape, stride=stride, plan=plan,
                            dataflow=sched.dataflow, epilogue=epi,
                            groups=groups)
    assert f"%{kernel_name(spec)}." in hlo

    rep = check_plan(cv, plan, precision=precision,
                     dataflow=sched.dataflow, epilogue=epi)
    assert rep.ok, rep.errors
    blocks = conv_working_set(cv, plan.nf_block, plan.c_block, plan.p_block,
                              width, dataflow=sched.dataflow, epilogue=epi)
    assert _granted_vmem(hlo) == [vmem_request_bytes(blocks)]
    return spec


@pytest.mark.parametrize("layer", sorted(VGG16))
def test_vgg16_kernel_compiles_for_v5e(one_chip, layer):
    spec = _compile_launch(one_chip, *VGG16[layer])
    # stages 3-5 (56-14 px) pack rows; 112 and 224 px rows would save no
    # 128-lane tile
    assert (spec.rows_per_dot > 1) == (VGG16[layer][2] <= 56)


@pytest.mark.parametrize("layer", sorted(RESNET18_PACKED))
def test_resnet18_packed_kernels_compile_for_v5e(one_chip, layer):
    """Packed rows lower: every tap dot of these launches covers k output
    rows of 4-16 lanes, laid end to end in the kernel."""
    spec = _compile_launch(one_chip, *RESNET18_PACKED[layer],
                           batch=RESNET18_BUCKET)
    assert spec.rows_per_dot > 1
    assert kernel_name(spec).endswith(f"_k{spec.rows_per_dot}")


@pytest.mark.parametrize("layer", sorted(OTHERS))
def test_strided_and_depthwise_kernels_compile_for_v5e(one_chip, layer):
    _compile_launch(one_chip, *OTHERS[layer])


@pytest.mark.parametrize("layer", sorted(MOBILENETV2_224))
def test_mobilenetv2_224_kernels_compile_for_v5e(one_chip, layer):
    _compile_launch(one_chip, *MOBILENETV2_224[layer],
                    batch=MOBILENETV2_BUCKET)


def _compile_forward(one_chip, monkeypatch, model, img, classes, bucket):
    """Compile one served forward at its bucket for the described chip:
    (the compiled network, its fold kernel launches by name, the
    compiled executable)."""
    import collections
    import functools

    from repro.core import engine
    from repro.models.zoo import get_conv_model
    monkeypatch.setattr(engine, "resolve_execution",
                        lambda policy="auto": ("pallas", False))
    spec = get_conv_model(model)
    shape = (bucket, 3, img, img)
    params = jax.eval_shape(functools.partial(
        spec.init_params, width_mult=1.0, img=img, classes=classes),
        jax.random.PRNGKey(0))
    net = engine.compile_network(params, spec.to_graph(), shape,
                                 policy="auto")
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), (params,
                                               jax.ShapeDtypeStruct(
                                                   shape, jnp.float32)))
    compiled = net.apply.lower(*args).compile()
    names = collections.Counter(re.findall(
        r"%(fold_[a-z]+_r\ds\d_st\d(?:_k\d+)?)\.\d+ = ", compiled.as_text()))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    return net, names


def _packed(names):
    return sum(n for name, n in names.items() if "_k" in name)


def test_mobilenetv2_224_forward_compiles_for_v5e(one_chip, monkeypatch):
    """The whole served forward at its bucket: one fold kernel per conv,
    the 17 depthwise convs on the depthwise kernel (strides 1 and 2), and
    its buffers within one chip's memory."""
    net, names = _compile_forward(one_chip, monkeypatch,
                                  "mobilenetv2_imagenet", 224, 1000,
                                  MOBILENETV2_BUCKET)
    assert net.fold_dataflows.count("depthwise") == 17
    assert sum(names.values()) == 52
    assert names["fold_dw_r3s3_st1"] + names["fold_dw_r3s3_st2"] == 17
    assert names["fold_dw_r3s3_st2"] == 4
    # the stride-1 1x1s over more than 128 channels pack rows
    assert _packed(names) == sum(k > 1 for k in net.fold_rows_per_dot) == 19


@pytest.mark.parametrize("model,img,classes,bucket,convs,packed", [
    ("vgg16", 224, 1000, 32, 13, 9),
    ("resnet18", 32, 10, 256, 20, 14),
])
def test_served_forward_packs_rows_on_v5e(one_chip, monkeypatch, model, img,
                                          classes, bucket, convs, packed):
    """VGG-16 and ResNet-18 compile whole at their served buckets, one
    fold kernel per conv: every stride-1 3x3 launch at 56 px and below
    packs output rows (VGG's stages 3-5; ResNet's stem and stages 1-4),
    within the VMEM each kernel asks for."""
    net, names = _compile_forward(one_chip, monkeypatch, model, img,
                                  classes, bucket)
    assert sum(names.values()) == len(net.fold_rows_per_dot) == convs
    assert _packed(names) == sum(k > 1 for k in net.fold_rows_per_dot) \
        == packed


@pytest.mark.xfail(strict=True, reason=(
    "Mosaic refuses the int8 stream's per-row input load: 'cannot "
    "statically prove that index in dimension 2 is a multiple of 8' — a "
    "packed int8 tile cannot be read one row at a dynamic offset "
    "(ROADMAP Speed 7)"))
def test_int8_kernel_compiles_for_v5e(one_chip):
    _compile_launch(one_chip, *VGG16["conv3_2"], precision="int8")
