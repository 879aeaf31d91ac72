"""The OTA slot's share of its roofline: the least time the chip needs
for the slots of the window (operations and bytes at the true, unpadded
shapes, `counting.ota_slot`) over the device time of the Pallas OTA
kernel's events, per chip. Nothing to read where the kernel did not run
(a padded node-count sweep takes the inline slot)."""
from chipbench import counting

KERNEL = r"ota_kernel|ota_edge_aggregate"


def read(ctx):
    run, t = ctx["run"], ctx["trace"]
    kernel_s = t.ops_matching(KERNEL)
    if kernel_s <= 0.0:
        return None
    slot = run["ota_slot"]
    least = slot["calls"] * counting.roofline_seconds(slot["work"],
                                                      run["peak"])
    return 100.0 * least / t.n_devices / kernel_s
