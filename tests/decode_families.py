"""Tiny zoo models of the five decode families, at the sizes tier-1 runs
them: the constructors the per-family test files and the family contract
(``test_decode_families.py``) share.  Each returns an initialised block
that ``serving.DecodeModel.from_block`` takes."""
import jax.numpy as jnp
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import (cohere2moe, gpt, olmo_hybrid, ouro,
                                       phi4flash)


def tiny_gpt(vocab_size):
    """2 layers, width 64, 4 heads, 128 positions."""
    net = gpt.GPTModel(vocab_size=vocab_size, num_layers=2, units=64,
                       hidden_size=128, num_heads=4, max_length=128,
                       dropout=0.0)
    net.initialize()
    net(mx.np.zeros((1, 4), dtype="int32"))
    return net


def _zoo_tiny(get):
    mx.random.seed(7)
    net = get("tiny")
    net.collect_params().setattr("grad_req", "null")
    net.initialize()
    return net


def tiny_phi4flash():
    """8 layers of all five kinds, width 64, window 8."""
    return _zoo_tiny(phi4flash.get_phi4flash)


def tiny_cohere2moe():
    """4 layers (one period), width 64, window 8, experts 4..8 of 16."""
    return _zoo_tiny(cohere2moe.get_cohere2moe)


def loud_ouro(params):
    """The seeded weights with gains, a gate and a bias that a wrong
    norm or a wrong exit rule cannot hide behind (gains of 1 and a zero
    gate are what the initialiser leaves)."""
    rng = np.random.default_rng(5)
    layers = dict(params["layers"], norm_g=jnp.asarray(
        rng.uniform(0.5, 1.5, params["layers"]["norm_g"].shape),
        jnp.float32))
    return dict(params, layers=layers, gate_w=params["gate_w"] * 20,
                gate_b=jnp.asarray([0.3], jnp.float32),
                lnf_g=jnp.asarray(rng.uniform(0.5, 1.5, (64,)),
                                  jnp.float32))


def tiny_ouro():
    """3 layers x 3 loop steps, 4 heads of 16, the weights loud."""
    net = _zoo_tiny(ouro.get_ouro)
    loud = loud_ouro(ouro._collect(net))
    net.norm_g.set_data(mx.nd.array(np.asarray(loud["layers"]["norm_g"])))
    net.ln_f_g.set_data(mx.nd.array(np.asarray(loud["lnf_g"])))
    net.gate_w.set_data(mx.nd.array(np.asarray(loud["gate_w"])))
    net.gate_b.set_data(mx.nd.array(np.asarray(loud["gate_b"])))
    return net


def loud_olmo_hybrid(params):
    """The seeded weights with norm gains and decays that a wrong norm
    or a wrong decay cannot hide behind (gains of 1 are what the
    initialiser leaves)."""
    rng = np.random.default_rng(5)

    def gains(a):
        return jnp.asarray(rng.uniform(0.5, 1.5, a.shape), jnp.float32)

    layers = [{k: gains(v) if k.endswith("_g") else v for k, v in p.items()}
              for p in params["layers"]]
    return dict(params, layers=layers, lnf_g=gains(params["lnf_g"]))


def tiny_olmo_hybrid():
    """One period (3 Gated DeltaNet layers and a full one), width 64,
    the norm gains loud."""
    net = _zoo_tiny(olmo_hybrid.get_olmo_hybrid)
    loud = loud_olmo_hybrid(olmo_hybrid._collect(net))
    for i, layer in enumerate(net.layers._children.values()):
        for name, a in loud["layers"][i].items():
            if name.endswith("_g"):
                getattr(layer, name).set_data(mx.nd.array(np.asarray(a)))
    net.ln_f_g.set_data(mx.nd.array(np.asarray(loud["lnf_g"])))
    return net


# by the family's name in ``DecodeModel.family``
TINY = {
    "gpt": lambda: tiny_gpt(97),
    "phi4flash": tiny_phi4flash,
    "cohere2moe": tiny_cohere2moe,
    "loop": tiny_ouro,
    "olmo_hybrid": tiny_olmo_hybrid,
}
