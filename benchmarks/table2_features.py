"""Survey Table 2: this framework's row in the libraries/platforms
comparison (criteria: baseline algorithms, environment integration,
parallel & distributed features)."""
from benchmarks.common import emit


def run():
    rows = [
        ("table2/baseline_algorithms", None,
         "DQN(+double+prioritized);PPO;IMPALA(V-trace);A3C;ES;DeepGA;ERL"),
        ("table2/environments", None,
         "CartPole;Pendulum;GridWorld;host-pipeline wrapper;"
         "LM-as-actor (10 assigned architectures)"),
        ("table2/topologies", None, "parameter-server;allreduce;gossip"),
        ("table2/synchronization", None,
         "BSP;ASP;SSP(bounded staleness);V-trace off-policy correction"),
        ("table2/parallel_features", None,
         "zero-copy batch simulation (vmap+scan);pjit/shard_map "
         "(pod,data,model) mesh;ZeRO-3 FSDP;expert parallelism;"
         "Pallas TPU kernels (flash-attn, wkv6, vtrace)"),
        ("table2/scale_proven", None,
         "512-chip multi-pod dry-run; 40 (arch x shape) baselines"),
    ]
    return emit(rows)
