"""The peak table (horovod_tpu/utils/roofline.py): the peaks of one chip are
never applied to another. Where a step's device time goes is
``hvd.metrics.profile_step``'s, held by tests/test_device_profile.py."""

import pytest

from horovod_tpu.utils.roofline import DEVICE_PEAKS, device_peaks


def test_unknown_device_kind_raises():
    """A device kind the table does not know is an error, not a default."""
    assert device_peaks("TPU v5 lite") is DEVICE_PEAKS["TPU v5 lite"]
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks("cpu")
    with pytest.raises(ValueError, match="TPU v9"):
        device_peaks("TPU v9")
