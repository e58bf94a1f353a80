"""The chips' published peaks, keyed by JAX's ``device_kind``. A device
that is not in ``peaks.json`` is an error, never a default."""
import json
from pathlib import Path

TABLE = json.loads((Path(__file__).with_name("peaks.json")).read_text())


def for_kind(kind: str) -> dict:
    try:
        return TABLE["devices"][kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(have {sorted(TABLE['devices'])})") from None
