"""``chip_smoke.py`` stays runnable: it refuses a machine without a TPU,
and its serving and logits phases pass on the CPU at tiny sizes (kernels
interpreted), so a change that breaks the script fails here before any
chip time is spent."""
import importlib.util
import pathlib

import jax

from repro.configs.registry import TINY_ARCHS

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_cpu(monkeypatch, capsys):
    cs = _load()
    monkeypatch.delenv("REPRO_ATTN_BACKEND", raising=False)
    monkeypatch.setattr("sys.argv", ["chip_smoke.py"])
    assert cs.main() == 1
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_phases_tiny(monkeypatch):
    cs = _load()
    monkeypatch.delenv("REPRO_ATTN_BACKEND", raising=False)  # pin backends
    cfg = TINY_ARCHS["qwen2-1.5b"]
    reqs = cs.make_requests(0, cfg.vocab_size, n=3, lens=(8, 40))
    sizes = dict(max_prompt_len=48, max_new=4, chunk=16, block=8,
                 page_size=4, num_pages=128)
    serve_u = cs.serve_config(unified=True, **sizes)
    serve_s = cs.serve_config(unified=False, **sizes)
    api_u, api_s = cs.build_api(cfg, serve_u), cs.build_api(cfg, serve_s)
    params = api_u.init_params(jax.random.PRNGKey(0))
    out_u, _ = cs.serve_phase("unified", api_u, serve_u, params, reqs)
    out_s, _ = cs.serve_phase("split", api_s, serve_s, params, reqs)
    assert out_u == out_s
    err = cs.logits_phase(cfg, params, reqs, serve_u, serve_s,
                          (api_u, api_s))
    assert err < 1e-4          # float32 tiny model: kernels == reference
