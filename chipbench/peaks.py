"""The table of peaks, keyed by device_kind.  An unknown device is an error."""
import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in {_PATH}: add its "
            "published figures with their source, do not default"
        )
    return table[device_kind]
