"""HLO text analysis: per-device FLOPs, memory bytes and collective wire
bytes of a compiled program (``hlo_analysis``)."""
