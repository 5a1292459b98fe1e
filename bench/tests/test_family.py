"""The family contract (``bench/reference/__init__.py``): a configuration's
``family`` alone chooses the module that gives its weights' leaves, its
plain reference and its work counts.

The weights and the reference logits of both tiny dense configurations,
and the leaf tables of both published ones, are pinned as the harness
made them before the dense family moved into its module.  A family
module placed in a checkout of its own is what the harness uses, for the
weights, the reference and the work counts, with no other file of the
harness edited; a family with no module is refused by ``load_cell``; a
family that gives no work counts leaves the metrics that need them out.
"""
import hashlib
import json
import os
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import reference as FAM
from bench import run as R
from bench import weights as W
from bench import work
from bench.tests import tiny

SEED = 2 ** 31 + 4242

# sha256 of the program's tree and of the reference's logits at SEED
PINNED = {
    "tiny-olmo": (
        "3716bf3b8f97e59ff2771837d2451b253ee628ccef2845ce76d984f77ee019ed",
        "cd984be4323f6e13e345e92bb5943780625257949bf145e27e4e69a57f21296c"),
    "tiny-nemotron": (
        "4e60e15b3e0ecf4bd3a107227b35e0b8b4f47dfd2affefb1ac8778b8bf57bc3f",
        "27207e4b7541a2b7ef347ff39a8ee011333c0834022277c42e360870d9882d6b"),
}
# sha256 of the sorted (path, shape, mean, std, dtype, stacked) table
PINNED_LEAVES = {
    "olmo-1b": (8, "b0dff73cb3c8602e1a790f5d2925d0fbf5862fcb3c523ab6b8329"
                   "34e531b05a5"),
    "nemotron-4-15b-stage8": (14, "da18e685d1896d9e11e8338df8771ebc352fee9"
                                  "89bab05028be979adc455b69a"),
}


def _tree_digest(tree) -> str:
    h = hashlib.sha256()
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, a in sorted(flat, key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(a)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("spec", [tiny.OLMO_LIKE, tiny.NEMOTRON_LIKE],
                         ids=["olmo", "nemotron"])
def test_weights_and_reference_logits_are_pinned(spec):
    params = W.program_params(spec, SEED)
    toks = np.random.default_rng(3).integers(0, spec["vocab_size"], (3, 12),
                                             dtype=np.int32)
    rows = np.array([(b, t) for b in range(3) for t in range(12)])
    logits = np.asarray(FAM.family(spec).logits_at(spec, SEED, toks, rows))
    assert logits.dtype == np.float32 and logits.shape == (36, 256)
    assert (_tree_digest(params),
            hashlib.sha256(logits.tobytes()).hexdigest()) == \
        PINNED[spec["name"]]


@pytest.mark.parametrize("name", sorted(PINNED_LEAVES))
def test_published_leaf_tables_are_pinned(name):
    cfg = json.load(open(os.path.join(R.ROOT, "bench", "configs",
                                      name + ".json")))
    leaves = FAM.family(cfg).leaves(cfg)
    table = sorted((lf.path, list(lf.shape), lf.mean, lf.std, lf.dtype,
                    lf.depth > 0) for lf in leaves)
    digest = hashlib.sha256(json.dumps(table).encode()).hexdigest()
    assert (len(table), digest) == PINNED_LEAVES[name]
    for lf in leaves:
        assert (lf.stack, lf.depth) in (("", 0),
                                        ("blocks", cfg["num_layers"]))


# a dense family that counts every call the harness makes into it
COUNTED = '''
import collections
from bench.reference import dense as _dense

CALLS = collections.Counter()


def _counted(name):
    fn = getattr(_dense, name)

    def call(*a, **kw):
        CALLS[name] += 1
        return fn(*a, **kw)
    return call


for _name in NAMES:
    globals()[_name] = _counted(_name)
'''
CONTRACT = ("leaves", "empty_subtrees", "logits_at")
COUNTS = ("layer_params", "head_params", "prefill_flops",
          "decode_token_flops", "decode_attn_work")


def _checkout(tmp_path, family: str, module_names=None) -> str:
    """A checkout holding one tiny cell whose configuration names
    ``family``, and (unless ``module_names`` is None) a family module
    that wraps the dense one's ``module_names``."""
    bench = json.load(open(os.path.join(R.ROOT, "BENCHMARK.json")))
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    bench["configs"] = [{"name": "tiny-plug", "source": "test",
                         "file": "bench/configs/tiny-plug.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": "tiny-plug", "config": "tiny-plug",
                           "traffic": "tiny-mix", "chips": 1,
                           "why": "test"}]
    spec = dict(tiny.OLMO_LIKE, name="tiny-plug", family=family)
    limits = spec.pop("limits")
    files = {"BENCHMARK.json": bench, "bench/configs/tiny-plug.json": spec,
             "bench/limits/tiny-plug.json": limits,
             "bench/traffic/tiny-mix.json": tiny.mix()}
    for rel, obj in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(obj))
    if module_names is not None:
        (tmp_path / "bench" / "reference").mkdir()
        (tmp_path / "bench" / "reference" / f"{family}.py").write_text(
            f"NAMES = {tuple(module_names)!r}\n" + COUNTED)
    return str(tmp_path)


def _reader_ctx(spec, mix, kernel_s=0.02):
    peak = R.peak_of("TPU v5 lite")
    red = SimpleNamespace(module_s=lambda name: (0.5, 4),
                          op_s=lambda name: kernel_s)
    toks = [(16, j) for j in range(1, 8)]
    return SimpleNamespace(spec=spec, mix=mix, peak=peak, red=red,
                           decode_tokens=lambda: toks)


def test_family_module_plugs_in(tmp_path):
    """load_cell -> make_params -> check() on a tiny run, and the work
    counts the readers take, all through the checkout's own module."""
    root = _checkout(tmp_path, "counted", CONTRACT + COUNTS)
    assert not os.path.exists(FAM.path_of("counted"))
    cell = R.load_cell("tiny-plug", root=root)
    spec = cell["spec"]
    fam = FAM.family(spec)
    assert os.path.realpath(fam.__file__) == os.path.realpath(
        os.path.join(root, "bench", "reference", "counted.py"))
    res = R.run("tiny-plug", SEED, 2.0, False, cell=cell,
                require_chip=False)
    assert res["correct"], res["checks"]
    assert fam.CALLS["leaves"] >= 1 and fam.CALLS["empty_subtrees"] >= 1
    assert fam.CALLS["logits_at"] == 1
    dense_spec = dict(tiny.OLMO_LIKE, name="tiny-plug")
    ctx = _reader_ctx(spec, cell["mix"])
    for name, count in (("prefill_mfu", "prefill_flops"),
                        ("decode_step_mfu", "decode_token_flops"),
                        ("decode_attn_roofline", "decode_attn_work")):
        before = fam.CALLS[count]
        got = R.read_layer_metric(name, ctx)
        assert fam.CALLS[count] > before, name
        assert got == R.read_layer_metric(
            name, _reader_ctx(dense_spec, cell["mix"])), name
    assert work.layer_params(spec) == work.layer_params(dense_spec)
    assert work.head_params(spec) == work.head_params(dense_spec)


def test_family_without_counts_leaves_their_metrics_out(tmp_path):
    root = _checkout(tmp_path, "uncounted", CONTRACT)
    cell = R.load_cell("tiny-plug", root=root)
    spec = cell["spec"]
    with pytest.raises(work.Uncounted):
        work.prefill_flops(spec, 16)
    ctx = _reader_ctx(spec, cell["mix"])
    for name in ("prefill_mfu", "decode_step_mfu", "decode_attn_roofline"):
        assert R.read_layer_metric(name, ctx) is None, name
    # a reader that needs no count still reads
    assert R.read_layer_metric("prefill_device_ms", ctx) == 125.0


@pytest.mark.parametrize("family,said", [
    ("moe", "bench/reference/moe.py does not exist"),
    ("../dense", "is not a module name")])
def test_family_without_module_is_refused(tmp_path, capsys, family, said):
    root = _checkout(tmp_path, family)
    with pytest.raises(SystemExit) as e:
        R.load_cell("tiny-plug", root=root)
    assert e.value.code == 2
    assert said in capsys.readouterr().err
