"""The VGG-16 reference against the program on the CPU: the FedAvg
traffic ``bench/traffic/ba33.fedavg.r1.json`` on
``bench/configs/vgg16.json``, cut to a test size, agrees with the
reference. (The VGG-16 cell is not in ``BENCHMARK.json``: on the chip no
number told its bfloat16 control from the program; see PERF.md.)"""
import small_cells as sc


def test_program_matches_reference(tmp_path):
    # float32 on the CPU: the two agree to rounding, which Adam's
    # normalised steps can grow to a few 1e-4 of a node's round loss; an
    # accuracy may differ by the predictions that rounding tipped
    limits = {"loss_gap": 0.01, "mean_loss_gap": 0.01, "iid_acc_gap": 0.05,
              "ood_acc_gap": 0.05}
    cell = sc.cut_to_size(sc.cell_from_files(
        "vgg16.ba33.fedavg", "vgg16", "ba33.fedavg.r1", 1, limits))
    with sc.compile_cache(tmp_path):
        out = sc.run(cell, 2_147_483_101)
    assert out["correct"], sc.dumps(out)
