from .gate import BaseGate, GShardGate, NaiveGate, SwitchGate
from .held_experts import HeldExpertsLayer
from .moe_layer import ExpertMLP, MoELayer

__all__ = ["MoELayer", "ExpertMLP", "HeldExpertsLayer", "BaseGate", "NaiveGate", "GShardGate", "SwitchGate"]
