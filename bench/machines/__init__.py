"""The plain reference's machines, one file per family, found by the
``family`` of a configuration's ``machine``.  Each holds a ``Machine``
class built from those numbers: link ids, ``capacity_gbs``,
``nic_link``, ``choices`` (the candidate draws, in the simulator's
order) and ``paths`` (the candidate paths, PAD-padded)."""
