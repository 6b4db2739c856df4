"""Whole-step share of the chip's int8 peak: images completed per second
times operations per image (``work.ops_per_image``), over the peak."""


def read(rec):
    peak = rec.get("peak")
    if not peak or "images_per_s" not in rec:
        return None
    return 100.0 * rec["images_per_s"] * rec["ops_per_image"] / peak["int8_ops"]
