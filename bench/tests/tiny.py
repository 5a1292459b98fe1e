"""Tiny cells for the CPU tests: the dense family at reduced widths, with
the harness's own traffic mixes cut to a few requests."""
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

OLMO_LIKE = dict(name="tiny-olmo", arch="olmo-1b", family="dense",
                 num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                 head_dim=16, d_ff=128, vocab_size=256,
                 norm_type="nonparametric", mlp_type="swiglu",
                 tie_embeddings=True, rope_theta=10000.0, dtype="float32",
                 limits={"logit_gap": 1e-5})
NEMOTRON_LIKE = dict(OLMO_LIKE, name="tiny-nemotron", arch="nemotron-4-15b",
                     num_kv_heads=2, norm_type="layernorm",
                     mlp_type="squared_relu", tie_embeddings=False)


def mix(rate=20.0, topology="pair", groups=("primary", "auxiliary"),
        split=0.5):
    return dict(arrival={"process": "poisson", "rate": rate}, prompt_len=16,
                output={"median": 6, "sigma": 0.6, "min": 2, "max": 12},
                serving={"topology": topology, "groups": list(groups),
                         "slots": 4, "split": split, "macro_steps": 4,
                         "queue_depth": 1024})


def cell(spec, mx, chips=1):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return {"cell": {"name": "tiny", "chips": chips}, "spec": spec,
            "mix": mx, "end_to_end": bench["end_to_end"],
            "per_layer": bench["per_layer"]}
