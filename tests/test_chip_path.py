"""What the chip path relies on, checked on the CPU: the compile-cache
placement, and node groups pinned to their own devices (4 forced host
devices, in a subprocess so this test process keeps one device)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.launch import compile_cache

REPO = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    ".."))


def test_compile_cache_env_dir_is_used_as_is(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses, json
    import jax, numpy as np
    import repro.core as C
    from repro.configs.base import get_config, reduced
    from repro.models import model as M
    from repro.serving.engine import ServeRequest

    cfg = dataclasses.replace(reduced(get_config("olmo-1b")), vocab_size=256)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    devs = jax.devices()
    rng = np.random.default_rng(0)
    reqs = [ServeRequest(uid=i, prompt=rng.integers(0, 256, 12)
                         .astype(np.int32), max_new=m, task="t")
            for i, m in enumerate([5, 3, 7, 4, 6, 2, 5, 3])]

    def groups(chips):
        return [C.NodeGroup(n, [d], C.JETSON_NANO)
                for n, d in zip(("hub", "s1", "s2", "s3"), chips)]

    def serve(topo):
        rt = C.HeteroRuntime(topo, slots=2, max_len=32, macro_steps=4)
        rt.add_task("t", cfg, params)
        where = {}
        for name, eng in rt.tasks["t"].engines.items():
            leaves = jax.tree.leaves((eng.params, eng.init_state()))
            where[name] = sorted({d.id for x in leaves
                                  for d in x.devices()})
        res = rt.serve(reqs, split=[1.0 / len(rt._decode)]
                       * len(rt._decode), warm=False)
        toks = {o.uid: o.tokens.tolist() for o in res.outputs["t"]}
        return where, toks, res.telemetry["totals"]

    out = {}
    star = lambda gs, **kw: C.Topology.star(gs[0], gs[1:], C.ICI_LINK, **kw)
    out["one_where"], ref, _ = serve(star(groups([devs[0]] * 4)))
    out["four_where"], toks, _ = serve(star(groups(devs)))
    out["star_match"] = toks == ref
    # disaggregated: prefill spoke s3 on device 3, blocks fetched to the
    # decode group's own device
    out["pf_where"], ptoks, tot = serve(star(groups(devs),
                                             prefill_spoke="s3"))
    out["pf_match"] = ptoks == ref
    out["pf_offloaded"] = tot["prefill_offloaded"]
    out["pf_fallbacks"] = tot["prefill_fallbacks"]
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def placed():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_groups_pinned_to_their_own_devices(placed):
    """Each decode group's params, KV cache and decode state live on
    its group's device, and the streams equal the same star served with
    every group on device 0."""
    assert placed["one_where"] == {n: [0] for n in ("hub", "s1", "s2",
                                                    "s3")}, placed
    assert placed["four_where"] == {"hub": [0], "s1": [1], "s2": [2],
                                    "s3": [3]}, placed
    assert placed["star_match"], placed


def test_prefill_blocks_fetched_to_the_decode_group(placed):
    """With a prefill spoke on its own device, every block is fetched
    onto the admitting group's device and the streams are unchanged."""
    assert placed["pf_where"] == {"hub": [0], "s1": [1], "s2": [2]}, placed
    assert placed["pf_match"], placed
    assert placed["pf_offloaded"] == 8, placed
    assert placed["pf_fallbacks"] == 0, placed
