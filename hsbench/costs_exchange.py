"""Least bytes of the mesh build's exchange, computed from the rows it has to
move and not from the slots the program ships. Kept with the yardstick, like
``costs.py``, so that no PR which speeds the collective up can also change
what it is measured against."""


def least_bytes_leaving_one_chip(valid_rows: float, n_chips: int, key_bytes: int,
                                 row_index_bytes: int = 4) -> float:
    """Rows are dealt evenly to the chips and a bucket's owner is ``bucket %
    n_chips``: a chip holds ``valid_rows / n_chips`` rows and all but one in
    ``n_chips`` of them belong to another chip. What has to travel with a row
    is its key, as wide as Arrow stores it, and the index that names the row
    to the host afterwards. Padding, the slot mask, the bucket id (the
    receiver can hash again) and planes wider than the key count for nothing:
    the share reads under 100 % by as much as the program ships beyond this."""
    if n_chips < 2:
        return 0.0
    per_chip = valid_rows / n_chips
    return per_chip * (key_bytes + row_index_bytes) * (n_chips - 1) / n_chips
