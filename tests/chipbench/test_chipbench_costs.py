"""The operation and byte counts against hand counts."""
import pytest

from chipbench import costs, harness


def test_tree_levels():
    assert costs.tree_levels((40, 20)) == [1, 40, 800]
    assert costs.tree_levels((15, 10, 5)) == [1, 15, 150, 750]


@pytest.mark.parametrize("fanouts,dims,fwd,bwd", [
    # 41 rows x 2 x (2*128*256), 1 row x 2 x (2*256*256), 2*256*64;
    # backward: layer 0's weights only, then weights and inputs
    ((40, 20), (128, 256, 64), 5_373_952 + 262_144 + 32_768,
     5_373_952 + 2 * 262_144 + 2 * 32_768),
    # 166 rows x 2 x (2*100*256), 16 rows and 1 row x 2 x (2*256*256),
    # 2*256*47
    ((15, 10, 5), (100, 256, 47),
     16_998_400 + 4_194_304 + 262_144 + 24_064,
     16_998_400 + 2 * (4_194_304 + 262_144) + 2 * 24_064),
    # one hop: only the seed level is convolved
    ((8,), (128, 256, 64), 131_072 + 32_768, 131_072 + 65_536),
])
def test_model_flops_per_seed(fanouts, dims, fwd, bwd):
    gcn = harness.family_module("gcn")
    model = dict(zip(("gcn_in_dim", "gcn_hidden", "n_classes"), dims))
    f = gcn.flops_per_seed(fanouts, model)
    assert f == {"forward": fwd, "backward": bwd}


def test_gen_min_bytes_hand_count():
    # one hop of 2 over 3 seeds on one worker, 4-wide rows, 5 distinct:
    # CSR 3 * (8 + 2*4) = 48; ids and masks 9 * 5 = 45; rows read
    # 5 * 16 = 80; rows written 9 * 16 = 144
    assert costs.gen_min_bytes((2,), 3, 1, 4, 5) == 48 + 45 + 80 + 144
    # every worker scans the whole frontier: W=2 doubles the CSR term
    assert costs.gen_min_bytes((2,), 3, 2, 4, 5) == 96 + 45 + 80 + 144
