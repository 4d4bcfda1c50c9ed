"""The reference's six aggregation strategies against the program, on the
CPU: the Fig. 4 grid of ``bench/traffic/fig4grid.mesh4.json`` (fl,
weighted, unweighted, random, degree, betweenness × two seeds) on the FFN,
cut to a test size and run on one device, agrees with the reference in
every experiment."""
import small_cells as sc


def test_fig4_grid_matches_reference(tmp_path):
    cell = sc.cut_to_size(sc.cell_from_files(
        "ffn3.fig4grid", "ffn3", "fig4grid.mesh4", 1, sc.CPU_LIMITS))
    cell.traffic.update(mesh_devices=0, check_experiments=12)
    with sc.compile_cache(tmp_path):
        out = sc.run(cell, 2_147_483_401)
    assert len(cell.traffic["strategies"]) * cell.traffic["seeds"] == 12
    assert out["correct"], sc.dumps(out)
