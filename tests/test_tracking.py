"""Tracker tests (reference parity: tests/test_tracking.py jsonl/tensorboard subset)."""

import json

from accelerate_tpu import Accelerator
from accelerate_tpu.tracking import GeneralTracker, JSONLTracker, filter_trackers


def test_jsonl_tracker_end_to_end(tmp_path):
    acc = Accelerator(log_with="jsonl", project_dir=str(tmp_path))
    acc.init_trackers("myrun", config={"lr": 0.1})
    acc.log({"loss": 1.5}, step=0)
    acc.log({"loss": 0.5}, step=1)
    acc.end_training()
    run_dir = tmp_path / "myrun"
    lines = [json.loads(l) for l in (run_dir / "metrics.jsonl").read_text().splitlines()]
    assert [l["loss"] for l in lines] == [1.5, 0.5]
    assert json.loads((run_dir / "config.json").read_text())["lr"] == 0.1


def test_filter_trackers_unknown_raises():
    import pytest

    with pytest.raises(ValueError, match="Unknown tracker"):
        filter_trackers("nope")


def test_custom_tracker_instance_passthrough():
    class MyTracker(GeneralTracker):
        name = "my"
        requires_logging_directory = False

        def __init__(self):
            super().__init__(_blank=True)
            self.logged = []

        @property
        def tracker(self):
            return None

        def store_init_configuration(self, values):
            self.config = values

        def log(self, values, step=None, **kwargs):
            self.logged.append((step, values))

    t = MyTracker()
    out = filter_trackers([t])
    assert out == [t]


def test_get_tracker():
    acc = Accelerator(log_with="jsonl", project_dir="/tmp/trk_test")
    acc.init_trackers("r1")
    assert acc.get_tracker("jsonl").name == "jsonl"


def test_jsonl_media_round_trip(tmp_path):
    """log_images / log_table / log_artifact on the dependency-free tracker: images land
    as .npy under media/ with a pointer row, tables inline in the metrics stream."""
    import numpy as np

    src = tmp_path / "extra.txt"
    src.write_text("payload")
    acc = Accelerator(log_with="jsonl", project_dir=str(tmp_path))
    acc.init_trackers("media_run")
    img = np.zeros((4, 6, 3), np.uint8)
    img[1, 2, 0] = 255
    acc.log_images({"val/sample": img}, step=3)
    acc.log_table("preds", columns=["id", "pred"], data=[[0, "a"], [1, "b"]], step=3)
    acc.log_artifact(str(src))
    acc.end_training()

    run_dir = tmp_path / "media_run"
    lines = [json.loads(l) for l in (run_dir / "metrics.jsonl").read_text().splitlines()]
    img_row = next(l for l in lines if "_images" in l)
    back = np.load(img_row["_images"]["val/sample"])
    np.testing.assert_array_equal(back, img)
    tbl_row = next(l for l in lines if "_table" in l)
    assert tbl_row["_table"]["columns"] == ["id", "pred"]
    assert tbl_row["_table"]["data"] == [[0, "a"], [1, "b"]]
    assert (run_dir / "artifacts" / "extra.txt").read_text() == "payload"


def test_tensorboard_media_round_trip(tmp_path):
    """An image and a table written through the TensorBoard tracker must
    be readable back from the offline event files (reference tracking.py:251,360)."""
    import numpy as np
    import pytest

    from accelerate_tpu.tracking import _AVAILABILITY, TensorBoardTracker

    if not _AVAILABILITY["tensorboard"]():
        pytest.skip("tensorboard not installed")
    t = TensorBoardTracker("tb_run", logging_dir=str(tmp_path))
    img = (np.linspace(0, 1, 4 * 6 * 3).reshape(4, 6, 3)).astype(np.float32)
    t.log_images({"val/sample": img}, step=1)
    t.log_table("preds", columns=["id", "pred"], data=[[0, "a"], [1, "b"]], step=1)
    t.finish()

    from tensorboard.backend.event_processing.event_accumulator import EventAccumulator

    acc = EventAccumulator(
        str(tmp_path / "tb_run"), size_guidance={"images": 0, "tensors": 0}
    )
    acc.Reload()
    assert any("val/sample" in tag for tag in acc.Tags().get("images", [])), acc.Tags()
    text_tags = acc.Tags().get("tensors", [])
    table_tag = next(tag for tag in text_tags if "preds" in tag)
    payload = acc.Tensors(table_tag)[0].tensor_proto.string_val[0].decode()
    assert "id" in payload and "pred" in payload and "| 0 | a |" in payload


def test_unsupported_media_warns_not_raises(caplog):
    """Backends without a media implementation inherit warn-and-skip no-ops — never a
    crash mid-training run."""

    class Minimal(GeneralTracker):
        name = "minimal"
        requires_logging_directory = False

        def __init__(self):
            super().__init__(_blank=True)

        @property
        def tracker(self):
            return None

        def store_init_configuration(self, values):
            pass

        def log(self, values, step=None, **kwargs):
            pass

    t = Minimal()
    import numpy as np

    t.log_images({"x": np.zeros((2, 2), np.uint8)})
    t.log_table("tbl", columns=["a"], data=[[1]])
    t.log_artifact("/nonexistent/file.txt")
