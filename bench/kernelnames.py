"""Which device operations of a trace are the uplink kernel.

The program launches its Pallas uplink (``repro.kernels.approx_channel``)
as a Mosaic custom call. On a TPU v5e its trace event is named by its HLO
text, ``%approx_channel_batch_aggregate_pallas.1 = (...) custom-call(...),
custom_call_target="tpu_custom_call", ...``. An operation that takes the
kernel's output names it among its operands, so only the event's own name,
left of `` = ``, and its custom-call target are matched. The round has no
other custom call.
"""

NAMES = ("approx_channel", "pallas")
TARGET = "tpu_custom_call"


def is_uplink_kernel(event) -> bool:
    """True for a trace event of the uplink kernel itself."""
    own, _, text = event.name.partition(" = ")
    if any(m in own.lower() for m in NAMES):
        return True
    if f'custom_call_target="{TARGET}"' in text:
        return True
    return any(TARGET in str(v) for v in event.stats.values())
