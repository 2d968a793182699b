"""3-layer GAT (Veličković et al., arXiv:1710.10903) at the widths of the
MLPerf Training GNN benchmark's R-GAT (mlcommons/training,
``graph_neural_network``, on IGBH), as recalled: 1024-wide node features,
hidden 512 as 4 heads of 128, fanouts (15, 10, 5), 2983 classes; one
relation, so plain GAT.  It keeps graphgen-gcn-deep's generation and
cache, so the fetch path moves rows 8x wider than the GCNs' and the
model takes real time next to generation."""
from ..core.config import ModelConfig

CONFIG = ModelConfig(
    name="graphgen-gat", family="gat",
    gcn_in_dim=1024, gcn_hidden=512, gat_heads=4, n_classes=2983,
    fanouts=(15, 10, 5),
    cache_rows=4096, cache_admit=2, cache_assoc=4, cache_mode="tiered",
    cache_l1_rows=512, cache_l1_promote=3,
)
