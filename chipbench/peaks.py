"""Published peaks of the chips the benchmark may meet, keyed by the
`device_kind` string JAX reports. A copy of `mxnet_tpu/chip.py`'s table,
kept here so that no later PR can move the yardstick. A device that is not
in the table is an error, never a default."""

import collections

ChipPeaks = collections.namedtuple(
    "ChipPeaks", ["bf16_flops", "hbm_bytes_per_s", "hbm_bytes"])

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 819 GB/s HBM bandwidth, 16 GB HBM per chip.
    "TPU v5 lite": ChipPeaks(197e12, 819e9, 16e9),
}


def peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError("no published peaks for device_kind %r: add a row "
                       "with its source to chipbench/peaks.py"
                       % (device_kind,))
