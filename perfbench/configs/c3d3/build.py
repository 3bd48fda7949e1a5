"""The system under test for ``c3d3``: 3C3D built from the repository's
module tree (``repro.nn`` layers in a ``repro.core`` Sequential)."""


def build(config):
    from repro.core.module import Activation, Dense, Sequential
    from repro.nn.layers import Conv2d, Flatten, MaxPool2d

    if config["pool_padding"] != "VALID":
        raise ValueError("MaxPool2d pools VALID windows only")
    mods, c, side = [], config["in_channels"], config["img"]
    for k, c_out, pad in zip(config["conv_kernels"], config["conv_channels"],
                             config["conv_padding"]):
        mods += [Conv2d(c, c_out, kernel=k, padding=pad), Activation("relu"),
                 MaxPool2d(config["pool_window"], config["pool_stride"])]
        side = side - k + 1 if pad == "VALID" else side
        side = (side - config["pool_window"]) // config["pool_stride"] + 1
        c = c_out
    mods.append(Flatten())
    d = side * side * c
    for w in config["dense"]:
        mods += [Dense(d, w), Activation("relu")]
        d = w
    mods.append(Dense(d, config["n_classes"]))
    return Sequential(mods)
