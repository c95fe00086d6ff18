"""Dataset substrate: synthetic analogues of the paper's four evaluation
datasets (``synth``) and the chunked point feeder (``pipeline.point_stream``)."""
