"""A two-layer decoder of Qwen3-MoE's form at test size, holding experts
4-7 of its router's 8: ``bench/configs/qwen3-30b-a3b.py`` run over the
sizes in ``tiny-moe.json`` (its configuration, plain float32 reference,
and operation and byte counts).
"""
from pathlib import Path

_QWEN3 = Path(__file__).resolve().parents[3] / "configs" / "qwen3-30b-a3b.py"
exec(compile(_QWEN3.read_text(), str(_QWEN3), "exec"))
