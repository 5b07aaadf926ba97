"""Analytic flash-attention correction: sanity, knob monotonicity, and
the fit check's corrected peak at a long causal sequence."""
import jax
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh

from repro.configs import get_config, get_reduced, get_shape
from repro.configs.base import ShapeConfig
from repro.core import costmodel
from repro.core.params import default_config
from repro.core.trial import CompileCache, RooflineEvaluator, Workload
from repro.models.layers import query_chunks


BASE = default_config(shard_strategy="fsdp_tp", compute_dtype="bfloat16",
                      attn_impl="pallas")


def test_zero_without_pallas():
    cfg, shp = get_config("glm4-9b"), get_shape("train_4k")
    rt = BASE.replace(attn_impl="xla")
    assert costmodel.flash_memory_correction_bytes(cfg, shp, rt, 16, 16) == 0
    assert costmodel.flash_peak_correction_bytes(cfg, shp, rt, 16, 16) == 0


def test_bigger_tiles_reduce_refetch():
    """file.buffer knob: larger q tiles -> fewer K/V refetches -> larger
    net traffic saving."""
    cfg, shp = get_config("glm4-9b"), get_shape("train_4k")
    small = costmodel.flash_memory_correction_bytes(
        cfg, shp, BASE.replace(attn_block_q=128), 16, 16)
    big = costmodel.flash_memory_correction_bytes(
        cfg, shp, BASE.replace(attn_block_q=512), 16, 16)
    assert big > small > 0


def test_remat_full_stores_fewer_scores():
    cfg, shp = get_config("glm4-9b"), get_shape("train_4k")
    none = costmodel.flash_peak_correction_bytes(cfg, shp, BASE, 16, 16)
    full = costmodel.flash_peak_correction_bytes(
        cfg, shp, BASE.replace(remat_policy="full"), 16, 16)
    assert none > full > 0            # none stores all layers' scores


def test_attention_shards_replicated_heads():
    """9 heads on a 16-wide model axis -> replicated over model."""
    smollm = get_config("smollm-135m")
    glm = get_config("glm4-9b")
    assert costmodel.attention_shards(smollm, BASE, 16, 16) == 16
    assert costmodel.attention_shards(glm, BASE, 16, 16) == 256
    bs = BASE.replace(attn_tp_fallback="batch_shard")
    assert costmodel.attention_shards(smollm, bs, 16, 16) == 256


def test_ssm_family_has_no_attention_apps():
    cfg = get_config("xlstm-1.3b")
    assert costmodel.attention_applications(cfg, get_shape("train_4k")) == []
    zam = get_config("zamba2-7b")
    apps = costmodel.attention_applications(zam, get_shape("train_4k"))
    assert apps == [(81 // 6, 4096, 4096)]


def test_decode_has_no_correction():
    cfg = get_config("glm4-9b")
    assert costmodel.attention_applications(cfg, get_shape("decode_32k")) \
        == []


class _LongCausal(Workload):
    """Reduced smollm-135m at 2 x 2048 tokens on the host mesh: causal
    XLA attention in query chunks, its scores nearly all of the peak."""

    def __init__(self, kind):
        super().__init__("smollm-135m", f"long_{kind}")
        self._kind = kind

    @property
    def cfg(self):
        return get_reduced(self.arch)

    @property
    def shp(self):
        return ShapeConfig("long", 2048, 2, self._kind)


def _one_device_mesh(**_):
    """One device, whatever device count the process was started with."""
    devices = np.array(jax.devices()[:1]).reshape(1, 1)
    return Mesh(devices, ("data", "model"), axis_types=(AxisType.Auto,) * 2)


@pytest.mark.parametrize("kind,remat", [("prefill", "none"),
                                        ("train", "full"),
                                        ("train", "none")])
def test_corrected_flash_peak_keeps_weights_and_state(kind, remat):
    """The Pallas peak (the XLA compile's, less the scores the flash
    kernel keeps out of HBM) stays above what the step must hold besides
    attention: the weights and the KV cache (prefill) or the weights,
    gradients and two AdamW moments (train), all float32, and above the
    2% floor that a correction removing more scores than the chunked XLA
    program holds would drop it to."""
    wl = _LongCausal(kind)
    cfg, B, S = wl.cfg, wl.shp.global_batch, wl.shp.seq_len
    assert len(query_chunks(S, S, causal=True)) > 1
    ev = RooflineEvaluator(mesh_factory=_one_device_mesh,
                           compile_cache=CompileCache(use_disk=False))
    rt = default_config(compute_dtype="bfloat16", remat_policy=remat,
                        attn_impl="pallas")
    xla = ev.calibrated_roofline(wl, rt.replace(attn_impl="xla"))
    flash = ev.calibrated_roofline(wl, rt)
    if kind == "train":
        held = 16 * cfg.param_count()
    else:
        held = 4 * cfg.param_count() + \
            2 * cfg.n_layers * B * S * cfg.n_kv_heads * cfg.hd * 4
    floor = 0.02 * xla.peak_mem_bytes
    assert max(held, floor) < flash.peak_mem_bytes < xla.peak_mem_bytes
