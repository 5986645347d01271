"""The contract every decode family keeps with the engine-facing
``serving.DecodeModel`` surface, one case a family: the class
``from_block`` picks, the step in the program table, the donation of
every buffer list the cache hands the step, and for a family that
cannot rewind a slot, the refusals by name.  The tiny models are
``tests/decode_families.py``'s."""
import os
import sys

import jax
import numpy as np
import pytest

from mxnet_tpu import serving, tracing
from mxnet_tpu.base import MXNetError

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from decode_families import TINY                              # noqa: E402

CLASSES = {
    "gpt": serving.gpt.GPTDecodeModel,
    "phi4flash": serving.hybrid.HybridDecodeModel,
    "cohere2moe": serving.moe.MoEDecodeModel,
    "loop": serving.loop.LoopDecodeModel,
    "olmo_hybrid": serving.delta.DeltaDecodeModel,
}
FAMILIES = list(CLASSES)
NO_ROLLBACK = [f for f in FAMILIES if not CLASSES[f].supports_rollback]
LIMITED = [f for f in FAMILIES if CLASSES[f].max_prompt]


@pytest.fixture(scope="module")
def built():
    return {}


@pytest.fixture
def model(family, built):
    if family not in built:
        built[family] = serving.DecodeModel.from_block(TINY[family]())
    return built[family]


def new_engine(model, **kw):
    kw = dict(dict(max_slots=4, kv_buckets=(64, 128), prefix_slots=0,
                   max_tokens=64), **kw)
    return serving.GenerationEngine(model, **kw)


def test_the_served_families_are_these_five():
    assert set(serving.DecodeModel._families.values()) == set(
        CLASSES.values())
    assert NO_ROLLBACK == ["phi4flash", "cohere2moe", "loop", "olmo_hybrid"]


@pytest.mark.parametrize("family", FAMILIES)
def test_from_block_returns_the_family_class(model, family):
    assert type(model) is CLASSES[family]
    assert isinstance(model, serving.DecodeModel)
    assert model.family == family
    # and the family itself builds from the block as the base does
    assert type(CLASSES[family].from_block(TINY[family]())) \
        is CLASSES[family]


@pytest.mark.parametrize("family", FAMILIES)
def test_the_program_table_records_the_step(model, family):
    roles = {(p.module, p.role) for p in tracing.programs()
             if p.family == family}
    assert {("jit__step", "decode"), ("jit__prefill", "prefill"),
            ("jit__select_one", "select"),
            ("jit__put_token", "select")} <= roles


@pytest.mark.parametrize("family", FAMILIES)
def test_the_step_donates_every_buffer_list(model, family):
    cache = model.make_cache(2, (64,), prefix_slots=0)
    buffers = cache.buffers()
    assert len(buffers) == model.cache_buffers
    S = cache.max_slots
    lowered = model._step_fn.lower(
        model.params, *buffers,
        model._step_tokens(np.zeros((S,), np.int32)),
        np.zeros((S,), np.int32),
        *model.device_sampling(model.greedy_sampling(S)))
    assert lowered.as_text().count("tf.aliasing_output") == len(
        jax.tree_util.tree_leaves(buffers))


@pytest.mark.parametrize("call", ["verify", "prefill_suffix"])
@pytest.mark.parametrize("family", NO_ROLLBACK)
def test_rollback_entry_points_raise(model, family, call):
    with pytest.raises(MXNetError, match=f"{family} family") as e:
        getattr(model, call)()
    assert model.no_rollback_why in str(e.value)


@pytest.mark.parametrize("kwargs,match", [
    (dict(spec_mode="self"), "spec_mode"),
    (dict(prefix_slots=2), "prefix_slots"),
    (dict(prefix_cache=serving.PrefixCache(2)), "prefix_slots"),
], ids=["spec_mode", "prefix_slots", "prefix_cache"])
@pytest.mark.parametrize("family", NO_ROLLBACK)
def test_speculation_and_prefix_cache_are_refused_by_name(model, family,
                                                          kwargs, match):
    with pytest.raises(MXNetError, match=f"{match}.*{family} family") as e:
        new_engine(model, **dict(dict(prefix_slots=None), **kwargs))
    assert model.no_rollback_why in str(e.value)


@pytest.mark.parametrize("family", LIMITED)
def test_a_prompt_past_the_prefill_limit_is_refused_at_submit(model,
                                                              family):
    engine = new_engine(model, kv_buckets=(64, 128, 2048))
    assert engine.prompt_buckets == (64, 128, 256, 512, 1024)
    long = np.zeros((1025,), np.int32)
    with pytest.raises(MXNetError, match="prefills in one program"):
        engine.submit(long, max_new_tokens=4)
