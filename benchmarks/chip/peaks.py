"""Published peaks of the chips the benchmark runs on (``peaks.json``),
keyed by JAX's ``device_kind``. A roofline share divides by these; a
device that is not in the table is an error, never a default."""
import json
import pathlib

TABLE = pathlib.Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    devices = json.loads(TABLE.read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {TABLE.name}")
    return devices[device_kind]
