"""Tables / native tier: rows over the create_schema + write +
block_until_ready wall in set-up."""


def read(view):
    return view["setup"]["rows"] / view["setup"]["load_s"]
