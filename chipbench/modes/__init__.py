"""Traffic modes: how a cell's calls drive the model and how their outputs
are checked.  A traffic file names its mode; ``chipbench/modes/<mode>.py``
defines ``Mode``."""
