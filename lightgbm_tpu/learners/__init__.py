from .serial import grow_tree, TreeLearnerParams

__all__ = ["grow_tree", "TreeLearnerParams"]
